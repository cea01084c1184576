"""GAT's blocked attention: the edge-softmax Pallas kernel (interpret mode)
against the jnp oracle and the float32 edge-list layer, and GAT served
through the engine against the benchmark's float64 reference.

Tolerances are stated against the output's scale, with their reasons:

* kernel vs jnp oracle, both float32: the same sums in another order, and
  the kernel's online softmax rescales each row's partial sums by
  exp(m_old - m_new) once per tile it visits; a few float32 ULP per visit
  over at most a handful of tiles stays under 2e-6 of the scale (ONLINE).
* blocked layer vs edge-list layer under "highest" precision: float32 with
  both projections exact to float32, so again a summation-order gap (LAYER).
* served model vs the float64 reference: two float32 layers (projection,
  softmax and weighted sum, each rounded to float32) and the ELU between
  them: 1e-5 of the scale is ~100 ULP, and still far under what bfloat16
  arithmetic reads (~4e-3), which the comparison must tell apart (SERVED).
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Graph, partition_graph, to_blocked
from repro.core.aggregate import (
    BlockedGraph,
    aggregate_backend,
    attention_aggregate_blocked,
)
from repro.gnn import build_model
from repro.gnn.layers import GATConv
from repro.kernels import ops
from repro.photonic.perf import GhostConfig
from repro.serving import Bucket, ExecutorPool, GnnServeEngine, ModelRegistry
from repro.serving.bucketing import bucket_for

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.models import gat as bench_gat  # noqa: E402
from bench.precision import Precision, max_error  # noqa: E402

ONLINE = 2e-6
LAYER = 2e-6
SERVED = 1e-5

HEADS = [pytest.param(8, 8, id="h8f8"), pytest.param(1, 7, id="h1f7")]
# Tiles: sublane-aligned, the serving default (padded to 24 in the kernel
# wrapper), and odd sizes with V != N.
TILES = [pytest.param(8, 8, id="t8x8"), pytest.param(20, 20, id="t20x20"),
         pytest.param(7, 9, id="t7x9")]


def special_graph(nv=44, ne=150, f=12, seed=0):
    """Random edges plus the cases the kernel must get right: vertex 0's
    in-edges come from sources in many tile columns (its softmax crosses
    tiles), vertex 5 is isolated (self term only), 3 -> 7 appears three
    times (multiplicity), and 9 and 11 carry loops (9 twice)."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, nv, ne)
    dst = rng.integers(0, nv, ne)
    keep = (src != 5) & (dst != 5) & (src != dst)
    src, dst = list(src[keep]), list(dst[keep])
    src += [1, 15, 22, 30, 43, 3, 3, 3, 9, 9, 11, 12]
    dst += [0, 0, 0, 0, 0, 7, 7, 7, 9, 9, 11, 11]
    return Graph(edge_src=np.asarray(src, np.int32),
                 edge_dst=np.asarray(dst, np.int32),
                 node_feat=rng.standard_normal((nv, f)).astype(np.float32)
                 ).validate()


def assert_close(got, ref, rel, err_msg=""):
    ref = np.asarray(ref)
    scale = float(np.max(np.abs(ref)))
    np.testing.assert_allclose(np.asarray(got), ref, rtol=0,
                               atol=rel * scale, err_msg=err_msg)


@pytest.mark.parametrize("v,n", TILES)
@pytest.mark.parametrize("heads,f", HEADS)
def test_kernel_matches_jnp_oracle(heads, f, v, n):
    """Scores spread wide (x4), so a row's running max moves between its
    tiles and the online rescaling is exercised."""
    g = special_graph()
    pg = partition_graph(g, v=v, n=n)
    bg = to_blocked(pg)
    rng = np.random.default_rng(1)
    vals = jnp.asarray(rng.standard_normal((pg.padded_src, heads, f)),
                       jnp.float32)
    s_src = jnp.asarray(4 * rng.standard_normal((pg.padded_src, heads)),
                        jnp.float32)
    s_dst = jnp.asarray(4 * rng.standard_normal((pg.padded_dst, heads)),
                        jnp.float32)
    ref = attention_aggregate_blocked(bg, vals, s_src, s_dst)
    got = ops.attention_block_spmm_padded(
        bg.blocks, bg.block_row, bg.block_col, vals, s_src, s_dst,
        bg.num_dst_groups, interpret=True)
    assert got.shape == ref.shape == (pg.padded_dst, heads, f)
    assert_close(got, ref, ONLINE)
    # The isolated vertex answers with its own value alone.
    np.testing.assert_array_equal(np.asarray(got[5]), np.asarray(vals[5]))
    assert int(np.max(pg.stats.max_tiles_per_row)) > 1


@pytest.mark.parametrize("v,n", TILES)
@pytest.mark.parametrize("heads,f", HEADS)
def test_blocked_layer_matches_edge_list(heads, f, v, n):
    """GATConv.apply_blocked on the jnp oracle and on both Pallas backends
    (the kernel) against the float32 edge-list GATConv.apply."""
    g = special_graph()
    p = GATConv.init(jax.random.PRNGKey(3), g.num_features, f, heads)
    p["b"] = 0.1 * jax.random.normal(jax.random.PRNGKey(4), (heads, f))
    pg = partition_graph(g, v=v, n=n)
    bg = to_blocked(pg)
    featp = jnp.asarray(pg.pad_features(g.node_feat))
    with jax.default_matmul_precision("highest"):
        ref = GATConv.apply(p, jnp.asarray(g.node_feat),
                            jnp.asarray(g.edge_src), jnp.asarray(g.edge_dst),
                            None, g.num_nodes)
        for backend in ("jnp", "pallas", "pallas_fused"):
            with aggregate_backend(backend):
                got = GATConv.apply_blocked(p, bg, featp)[:g.num_nodes]
            assert_close(got, ref, LAYER, err_msg=backend)


def test_loop_rule_and_multiplicity_in_the_edge_list_layer():
    """A loop changes nothing (the self term stands for it, once); a
    duplicate edge weighs as two."""
    rng = np.random.default_rng(2)
    feat = jnp.asarray(rng.standard_normal((4, 6)), jnp.float32)
    p = GATConv.init(jax.random.PRNGKey(0), 6, 3, 2)

    def out(src, dst):
        return GATConv.apply(p, feat, jnp.asarray(src, jnp.int32),
                             jnp.asarray(dst, jnp.int32), None, 4)

    base = out([0, 1, 2], [1, 2, 3])
    np.testing.assert_allclose(out([0, 1, 2, 2, 2], [1, 2, 3, 2, 2]), base,
                               rtol=1e-6, atol=1e-6)
    twice = out([0, 0, 1, 2], [1, 1, 2, 3])
    assert not np.allclose(twice[1], base[1])
    np.testing.assert_allclose(twice[2:], base[2:], rtol=1e-6, atol=1e-6)


def _stack(graphs, v, n, bucket_blocks, g_dst, g_src, f):
    """Slots as the engine stacks them: tiles padded with zero tiles on
    the last group, features with zero rows; ``None`` is an empty slot."""
    blocks, rows, cols, feats = [], [], [], []
    for g in graphs:
        b = np.zeros((bucket_blocks, v, n), np.float32)
        r = np.full(bucket_blocks, g_dst - 1, np.int32)
        c = np.full(bucket_blocks, g_src - 1, np.int32)
        x = np.zeros((g_src * n, f), np.float32)
        if g is not None:
            pg = partition_graph(g, v=v, n=n)
            k = pg.blocks.shape[0]
            b[:k], r[:k], c[:k] = pg.blocks, pg.block_row, pg.block_col
            x[:g.num_nodes] = g.node_feat
        blocks.append(b)
        rows.append(r)
        cols.append(c)
        feats.append(x)
    return tuple(jnp.asarray(np.stack(a)) for a in (blocks, rows, cols, feats))


def test_vmapped_slots_with_an_empty_one():
    """The kernel under vmap, as the executor runs it: two graphs and an
    empty padding slot, against the jnp oracle slot by slot."""
    v = n = 8
    graphs = [special_graph(seed=0), special_graph(ne=90, seed=4), None]
    model = build_model("gat", 12, 7, hidden=8, heads=8)
    params = model.init(jax.random.PRNGKey(0))
    arrays = _stack(graphs, v, n, 64, 6, 6, 12)

    def fwd(blocks, row, col, feat):
        bg = BlockedGraph(blocks=blocks, block_row=row, block_col=col,
                          num_dst_groups=6, num_src_groups=6, v=v, n=n,
                          num_nodes=48)
        return model.apply_blocked(params, bg, feat)

    with aggregate_backend("pallas_fused"):
        got = jax.jit(jax.vmap(fwd))(*arrays)
    ref = jax.jit(jax.vmap(fwd))(*arrays)
    assert np.all(np.isfinite(np.asarray(got)))
    for i in range(3):
        assert_close(got[i], ref[i], ONLINE, err_msg=f"slot {i}")


def test_served_gat_matches_float64_reference():
    """try_submit -> executor pool on pallas_fused -> result, against
    bench/models/gat.py at float64, with the benchmark's weight layout."""
    cfg = {"num_features": 12, "hidden": 8, "heads": 8, "num_classes": 7}
    model = bench_gat.program_model(cfg)
    params = bench_gat.init_params(cfg, jax.random.PRNGKey(5))
    host = jax.tree.map(lambda x: np.asarray(x, np.float64), params)
    graphs = [special_graph(seed=s) for s in range(3)]
    eng = GnnServeEngine(cfg=GhostConfig(v=8, n=8), slots=2,
                         backend="pallas_fused")
    eng.register("gat", model, params)
    eng.start()
    try:
        rids = [eng.try_submit("gat", g) for g in graphs]
        outs = [eng.result(rid, timeout=300) for rid in rids]
    finally:
        eng.stop()
    for g, out in zip(graphs, outs):
        edges = bench_gat.normalized_edges(g.edge_src, g.edge_dst,
                                           g.num_nodes)
        ref = bench_gat.forward(host, g.node_feat, edges, g.num_nodes,
                                Precision("float64"))
        assert max_error(out, ref) <= SERVED
        # The comparison tells a bfloat16 computation apart.
        low = bench_gat.forward(host, g.node_feat, edges, g.num_nodes,
                                Precision("bfloat16"))
        assert max_error(low, ref) > 10 * SERVED


def test_attention_counters_on_records():
    """Each record of a GAT batch carries the batch's attended entries
    (edges that are not loops plus self terms, per layer) and the entries
    the kernel swept (slots x tiles x V x N per layer); other models 0."""
    g = special_graph()
    loops = int(np.sum(g.edge_src == g.edge_dst))
    gat = build_model("gat", 12, 3, hidden=4, heads=2)
    gcn = build_model("gcn", 12, 3, hidden=4)
    eng = GnnServeEngine(cfg=GhostConfig(v=8, n=8), slots=2)
    eng.register("gat", gat, gat.init(jax.random.PRNGKey(0)))
    eng.register("gcn", gcn, gcn.init(jax.random.PRNGKey(1)))
    eng.submit("gat", g)
    eng.submit("gat", g)
    eng.submit("gcn", g)
    eng.drain()
    by_model = {}
    for r in eng.records:
        by_model.setdefault(r.model_id, []).append(r)
    assert len(by_model["gat"]) == 2
    num_blocks = bucket_for(partition_graph(g, v=8, n=8)).num_blocks
    for r in by_model["gat"]:
        # Two requests in one batch of two slots, two attention layers.
        assert r.batch_size == 2
        assert r.attn_edges == 2 * 2 * (g.num_edges - loops + g.num_nodes)
        assert r.attn_entries == 2 * 2 * num_blocks * 8 * 8
    assert all(r.attn_edges == r.attn_entries == 0 for r in by_model["gcn"])


def test_gat_executor_lowers_to_the_attention_kernel():
    """On both Pallas backends the executor's program holds the attention
    kernel: no silent fallback to the jnp oracle."""
    model = build_model("gat", 12, 7, hidden=8, heads=8)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    entry = ModelRegistry().register("gat", model, params, task="node")
    bucket = Bucket(num_blocks=16, num_dst_groups=4, num_src_groups=4,
                    v=20, n=20, f=16)
    for backend in ("pallas", "pallas_fused", "jnp"):
        pool = ExecutorPool(slots=2, backend=backend)
        text = str(pool.lower(entry, bucket).as_text())
        assert ("attention_block_spmm" in text) == (backend != "jnp"), backend
