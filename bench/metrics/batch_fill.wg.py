"""Mean requests per executed batch over the slots of a batch (%)."""


def read(ctx):
    return ctx.batch_fill_pct()
