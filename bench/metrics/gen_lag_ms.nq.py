"""95th percentile of how late the load generator sent a request (ms)."""


def read(ctx):
    return ctx.lag_p95_ms()
