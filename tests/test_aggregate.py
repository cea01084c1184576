"""Blocked (GHOST) aggregation == edge-list oracle, all reduce ops."""

import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, st

from repro.core import (
    Graph,
    ReduceOp,
    aggregate_blocked,
    aggregate_edges,
    attention_aggregate_blocked,
    partition_graph,
    to_blocked,
)


def make_graph(seed, nv, ne, f):
    rng = np.random.default_rng(seed)
    return Graph(
        edge_src=rng.integers(0, nv, ne).astype(np.int32),
        edge_dst=rng.integers(0, nv, ne).astype(np.int32),
        node_feat=rng.standard_normal((nv, f)).astype(np.float32),
    ).validate()


@pytest.mark.parametrize("reduce", [ReduceOp.SUM, ReduceOp.MEAN, ReduceOp.MAX])
@pytest.mark.parametrize("v,n", [(8, 8), (5, 11), (16, 3)])
def test_blocked_matches_edges(reduce, v, n):
    g = make_graph(0, nv=73, ne=300, f=9)
    pg = partition_graph(g, v=v, n=n)
    bg = to_blocked(pg)
    featp = jnp.asarray(pg.pad_features(g.node_feat))
    ref = aggregate_edges(jnp.asarray(g.edge_src), jnp.asarray(g.edge_dst),
                          jnp.asarray(g.node_feat), g.num_nodes, reduce)
    got = aggregate_blocked(bg, featp, reduce)[:g.num_nodes]
    np.testing.assert_allclose(got, ref, atol=1e-4)


@given(st.integers(0, 500))
def test_blocked_sum_property(seed):
    rng = np.random.default_rng(seed)
    nv = int(rng.integers(5, 60))
    ne = int(rng.integers(1, 150))
    g = make_graph(seed, nv, ne, 5)
    v, n = int(rng.integers(1, 12)), int(rng.integers(1, 12))
    pg = partition_graph(g, v=v, n=n)
    bg = to_blocked(pg)
    featp = jnp.asarray(pg.pad_features(g.node_feat))
    ref = aggregate_edges(jnp.asarray(g.edge_src), jnp.asarray(g.edge_dst),
                          jnp.asarray(g.node_feat), nv, ReduceOp.SUM)
    got = aggregate_blocked(bg, featp, ReduceOp.SUM)[:nv]
    np.testing.assert_allclose(got, ref, atol=1e-4)


def test_weighted_sum_gcn_norm():
    g = make_graph(1, 40, 150, 6).with_self_loops()
    w = g.gcn_edge_weights()
    pg = partition_graph(g, v=8, n=8, edge_weights=w)
    bg = to_blocked(pg)
    featp = jnp.asarray(pg.pad_features(g.node_feat))
    ref = aggregate_edges(jnp.asarray(g.edge_src), jnp.asarray(g.edge_dst),
                          jnp.asarray(g.node_feat), g.num_nodes,
                          ReduceOp.SUM, jnp.asarray(w))
    got = aggregate_blocked(bg, featp, ReduceOp.SUM)[:g.num_nodes]
    np.testing.assert_allclose(got, ref, atol=1e-4)


def test_attention_aggregate_matches_segment_softmax():
    """Blocked GAT softmax-aggregation == explicit edge-list computation
    over N(v) u {v}: loops in the graph dropped, one self term per vertex."""
    g = make_graph(2, 30, 120, 4)
    heads, f = 3, 4
    rng = np.random.default_rng(0)
    vals = rng.standard_normal((g.num_nodes, heads, f)).astype(np.float32)
    s_src = rng.standard_normal((g.num_nodes, heads)).astype(np.float32)
    s_dst = rng.standard_normal((g.num_nodes, heads)).astype(np.float32)

    # edge-list reference on the attended entries
    import jax
    keep = g.edge_src != g.edge_dst
    nodes = np.arange(g.num_nodes)
    src = jnp.asarray(np.concatenate([g.edge_src[keep], nodes]))
    dst = jnp.asarray(np.concatenate([g.edge_dst[keep], nodes]))
    logits = jax.nn.leaky_relu(
        jnp.asarray(s_dst)[dst] + jnp.asarray(s_src)[src], 0.2)
    m = jax.ops.segment_max(logits, dst, num_segments=g.num_nodes)
    z = jnp.exp(logits - m[dst])
    denom = jax.ops.segment_sum(z, dst, num_segments=g.num_nodes)
    alpha = z / denom[dst]
    ref = jax.ops.segment_sum(alpha[..., None] * jnp.asarray(vals)[src],
                              dst, num_segments=g.num_nodes)

    pg = partition_graph(g, v=7, n=9)
    bg = to_blocked(pg)
    pad_src = pg.padded_src
    pad_dst = pg.padded_dst
    vals_p = jnp.asarray(np.pad(vals, ((0, pad_src - g.num_nodes), (0, 0), (0, 0))))
    ssrc_p = jnp.asarray(np.pad(s_src, ((0, pad_src - g.num_nodes), (0, 0))))
    sdst_p = jnp.asarray(np.pad(s_dst, ((0, pad_dst - g.num_nodes), (0, 0))))
    got = attention_aggregate_blocked(bg, vals_p, ssrc_p, sdst_p)[:g.num_nodes]
    np.testing.assert_allclose(got, ref, atol=1e-4)
