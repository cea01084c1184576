"""Median time a stacked whole-graph batch waited for an executor and the
device lock, ``RequestRecord.device_wait_s`` (ms)."""

from bench import loops


def read(ctx):
    per_batch = {(i, r.batch): r.device_wait_s
                 for i, recs in enumerate(ctx.replica_records) for r in recs
                 if not r.node_query and hasattr(r, "device_wait_s")}
    spans = list(per_batch.values())
    return loops.percentile(spans, 50) * 1e3 if spans else None
