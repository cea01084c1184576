"""Share of node-query batches stacked into a reused buffer set,
``RequestRecord.stack_reused``, each batch counted once (%)."""


def read(ctx):
    per_batch = {(i, r.batch): r.stack_reused
                 for i, recs in enumerate(ctx.replica_records) for r in recs
                 if r.node_query and hasattr(r, "stack_reused")}
    if not per_batch:
        return None
    return 100.0 * sum(per_batch.values()) / len(per_batch)
