"""Median host time of k-hop sampling per query,
``RequestRecord.sample_s`` (ms)."""


def read(ctx):
    return ctx.sample_p50_ms()
