"""Mean bytes per node-query batch handed to ``device_put``: the whole
stacked batch, empty slots included, ``RequestRecord.h2d_bytes`` (MB)."""


def read(ctx):
    per_batch = {(i, r.batch): r.h2d_bytes
                 for i, recs in enumerate(ctx.replica_records) for r in recs
                 if r.node_query and hasattr(r, "h2d_bytes")}
    if not per_batch:
        return None
    return sum(per_batch.values()) / len(per_batch) / 1e6
