"""Median host time of a whole-graph request's preprocessing: cache
lookup or partition, bucket and tile padding, feature padding,
``RequestRecord.prep_s`` (ms)."""

from bench import loops


def read(ctx):
    spans = [r.prep_s for r in ctx.records
             if not r.node_query and hasattr(r, "prep_s")]
    return loops.percentile(spans, 50) * 1e3 if spans else None
