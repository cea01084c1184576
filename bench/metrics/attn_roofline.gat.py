"""Least time of the GAT attention work served, at the chip's peaks, over
the Pallas kernels' device time (%).  The work is counted from the real
graph at the published widths (``bench/models/gat.py``); in GAT's program
the blocked attention kernel is the only Pallas kernel."""

from bench import counts, spec


def read(ctx):
    kernel_s = ctx.trace.kernel_s if ctx.trace else 0.0
    if kernel_s <= 0 or not ctx.work:
        return None
    gat = spec.load_module("models", "gat")
    # The context carries each layer's widths; the head count is the
    # configuration's.
    heads = spec.load_json(spec.BENCH_DIR / "configs" / "gat-cora.json")[
        "heads"]
    shapes = gat.head_shapes(ctx.dims, heads)
    flops = nbytes = 0.0
    for v, e in ctx.work:
        f, b = gat.attention_work(shapes, v, e)
        flops += f
        nbytes += b
    least, _ = counts.least_time_s(flops, nbytes, ctx.peak)
    return 100.0 * least / kernel_s
