"""Share of the tile entries the attention kernel swept that are real
attended entries (edges plus self terms), ``RequestRecord.attn_edges`` over
``attn_entries``, each batch counted once (%)."""


def read(ctx):
    per_batch = {(i, r.batch): (r.attn_edges, r.attn_entries)
                 for i, recs in enumerate(ctx.replica_records) for r in recs
                 if getattr(r, "attn_entries", 0)}
    if not per_batch:
        return None
    edges = sum(e for e, _ in per_batch.values())
    entries = sum(n for _, n in per_batch.values())
    return 100.0 * edges / entries
