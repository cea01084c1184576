"""The GAT cell's readers, its registration, the count of attention work,
and the reference's neighbourhood, on synthetic records and small graphs."""

from types import SimpleNamespace

import numpy as np
import pytest

from bench import context, counts, spec
from bench.precision import Precision

ATTN = ("attn_roofline.gat", "attn_fill.gat")
CELL = "gat-cora.closed"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def rec(batch, **fields):
    return SimpleNamespace(node_query=False, batch=batch, **fields)


def ctx_of(replica_records, work=(), dims=(), trace=None):
    return context.Context(
        outcomes=[], slots=4, replica_records=replica_records,
        stack_busy_s=0.0, exec_busy_s=0.0, cache_hits=0, cache_misses=0,
        work=list(work), dims=list(dims), combines_per_layer=1, peak=PEAK,
        trace=trace)


def read(name, ctx):
    return spec.load_module("metrics", name).read(ctx)


def gat():
    return spec.load_module("models", "gat")


def test_attn_fill_counts_each_batch_once():
    # Replica 0: batch 0 of two requests (the batch's values on both
    # records), batch 1 of one; replica 1's batch 0 is another batch.
    replicas = [
        [rec(0, attn_edges=30, attn_entries=1000),
         rec(0, attn_edges=30, attn_entries=1000),
         rec(1, attn_edges=10, attn_entries=1000)],
        [rec(0, attn_edges=20, attn_entries=2000)],
    ]
    assert read("attn_fill.gat", ctx_of(replicas)) == pytest.approx(
        100.0 * 60 / 4000)


@pytest.mark.parametrize("name", ATTN)
def test_attention_readers_are_silent_without_the_fields(name):
    # A program without the counters, one whose batches ran no attention,
    # a run with no records, and a trace with no Pallas kernel time.
    work = [(2708, 13264)]
    dims = [(1433, 64), (64, 7)]
    no_kernel = SimpleNamespace(kernel_s=0.0)
    assert read(name, ctx_of([[rec(0)]], work, dims)) is None
    assert read(name, ctx_of([[rec(0, attn_edges=0, attn_entries=0)]],
                             work, dims, no_kernel)) is None
    assert read(name, ctx_of([[]], (), dims, no_kernel)) is None


def test_attention_metrics_are_registered_for_the_gat_cell_only():
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    for name in ATTN:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["layer"] == "attention kernel"
        assert entry["unit"] == "%" and entry["moves"] == "throughput_rps"
        assert entry["workloads"] == [CELL]
    for w in bench["workloads"]:
        names = {m["name"] for m in spec.load_cell(w["name"]).per_layer}
        assert (w["name"] == CELL) == bool(names & set(ATTN))
    cell = spec.load_cell(CELL)
    assert cell.chips == 1 and cell.config["arch"] == "gat"
    assert {m["name"] for m in cell.end_to_end} == {"throughput_rps",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} == set(ATTN) | {
        "stack_ms.wg", "h2d_mb.wg", "device_wait_ms.wg", "mfu.wg"}


def test_attention_work_against_hand_arithmetic():
    # Cora at the published widths: 8 heads of 8, then 1 head of 7, over
    # 2,708 vertices and 10,556 edges plus 2,708 self terms.
    shapes = gat().head_shapes([(1433, 64), (64, 7)], 8)
    assert shapes == [(8, 8), (1, 7)]
    v, e = 2708, 13264
    flops, nbytes = gat().attention_work(shapes, v, e)
    assert flops == e * 8 * (2 * 8 + 6) + e * 1 * (2 * 7 + 6)
    assert nbytes == (4 * e + 4 * v * 8 * (2 * 8 + 2)
                      + 4 * e + 4 * v * 1 * (2 * 7 + 2))
    # The roofline share: memory-bound here, over 2 ms of kernel time for
    # two requests.
    least, bound = counts.least_time_s(2 * flops, 2 * nbytes, PEAK)
    assert bound == "memory"
    ctx = ctx_of([[]], [(v, e), (v, e)], [(1433, 64), (64, 7)],
                 SimpleNamespace(kernel_s=2e-3))
    assert read("attn_roofline.gat", ctx) == pytest.approx(
        100.0 * least / 2e-3)
    assert read("attn_roofline.gat", ctx) < 100.0


def test_reference_attends_over_neighbours_and_self():
    # Loops are dropped and one self term per vertex added; a duplicate
    # edge stays, so it weighs twice.
    src, dst, w = gat().normalized_edges(np.array([0, 1, 1, 2]),
                                         np.array([1, 2, 2, 2]), 3)
    assert sorted(zip(src.tolist(), dst.tolist())) == [
        (0, 0), (0, 1), (1, 1), (1, 2), (1, 2), (2, 2)]
    assert np.all(w == 1.0)


def test_reference_is_the_programs_function():
    """The float64 reference and the program's float32 edge-list layer at
    "highest" precision, on the benchmark's weight layout (to float32's
    rounding of the output scale)."""
    import jax
    import jax.numpy as jnp

    cfg = {"num_features": 10, "hidden": 4, "heads": 3, "num_classes": 5}
    rng = np.random.default_rng(0)
    nv = 30
    src = rng.integers(0, nv, 120)
    dst = rng.integers(0, nv, 120)
    feat = rng.standard_normal((nv, 10)).astype(np.float32)
    params = gat().init_params(cfg, jax.random.PRNGKey(1))
    model = gat().program_model(cfg)
    with jax.default_matmul_precision("highest"):
        got = model.apply(params, jnp.asarray(feat), jnp.asarray(src),
                          jnp.asarray(dst), None, nv)
    host = jax.tree.map(lambda x: np.asarray(x, np.float64), params)
    ref = gat().forward(host, feat, gat().normalized_edges(src, dst, nv), nv,
                        Precision("float64"))
    scale = np.max(np.abs(ref))
    assert np.max(np.abs(np.asarray(got) - ref)) <= 1e-5 * scale


def test_a_program_without_the_self_term_fails_at_build(monkeypatch):
    """A program whose GAT gives a vertex with no edge nothing of its own
    (no self term) is refused before any engine is built."""
    import repro.gnn

    real = repro.gnn.build_model

    class NoSelfTerm:
        def __init__(self, model):
            self.model = model

        def init(self, key):
            return self.model.init(key)

        def apply(self, params, feat, *args, **kw):
            return np.zeros((feat.shape[0], 1))

    monkeypatch.setattr(repro.gnn, "build_model",
                        lambda *a, **k: NoSelfTerm(real(*a, **k)))
    with pytest.raises(RuntimeError, match="does not attend"):
        gat().program_model({"num_features": 4, "hidden": 2, "heads": 2,
                             "num_classes": 3})
