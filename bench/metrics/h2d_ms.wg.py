"""Median time per whole-graph batch to copy its stacked inputs to the device,
``RequestRecord.h2d_s`` (ms)."""

from bench import loops


def read(ctx):
    per_batch = {(i, r.batch): r.h2d_s
                 for i, recs in enumerate(ctx.replica_records) for r in recs
                 if not r.node_query and hasattr(r, "h2d_s")}
    spans = list(per_batch.values())
    return loops.percentile(spans, 50) * 1e3 if spans else None
