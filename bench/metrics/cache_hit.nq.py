"""Share of requests whose partition the preprocess cache held (%)."""


def read(ctx):
    return ctx.cache_hit_pct()
