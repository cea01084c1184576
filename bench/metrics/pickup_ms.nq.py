"""95th percentile of the time a node query's answer sat published before
``result()`` handed it over, ``RequestRecord.pickup_s`` (ms)."""

from bench import loops


def read(ctx):
    spans = [r.pickup_s for r in ctx.records
             if r.node_query and getattr(r, "pickup_s", None) is not None]
    return loops.percentile(spans, 95) * 1e3 if spans else None
