"""Least time of the aggregation work served, at the chip's peaks, over
the aggregation kernels' device time (%)."""


def read(ctx):
    share = ctx.agg_roofline()
    return None if share is None else share[0]
