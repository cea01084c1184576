"""Multi-model serving: registry, executor pool, schedulers, admission.

The load-bearing claims of the multi-model refactor:
  * one engine serves a heterogeneous catalog (>= 3 models, >= 2 feature
    dims) with every per-request output bit-exact vs that model's own
    unbatched ``apply_blocked`` at fp32;
  * the jit-trace count stays <= |models| x |buckets observed|;
  * the FIFO scheduler preserves head-of-line order; the occupancy-aware
    scheduler serves the fullest group and its age bound prevents
    starvation under sustained load;
  * admission control bounds the waiting queue with working reject and
    shed-oldest policies, surfaced in the report;
  * MAX-reduce models ride the jnp fallback inside a Pallas-backend
    executor, and zero-edge graphs serve through the catalog path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Graph,
    ReduceOp,
    aggregate_backend,
    aggregate_blocked,
    partition_graph,
    to_blocked,
)
from repro.gnn import build_model
from repro.photonic.perf import GhostConfig
from repro.serving import (
    FifoScheduler,
    GnnServeEngine,
    GroupState,
    OccupancyScheduler,
    QueueFullError,
    gcn_prepare,
    make_scheduler,
)

from tests._tolerance import assert_ulp_close


def make_graph(seed, nv=None, ne=None, f=7):
    rng = np.random.default_rng(seed)
    nv = nv or int(rng.integers(6, 70))
    ne = ne or int(rng.integers(1, 200))
    return Graph(
        edge_src=rng.integers(0, nv, ne).astype(np.int32),
        edge_dst=rng.integers(0, nv, ne).astype(np.int32),
        node_feat=rng.standard_normal((nv, f)).astype(np.float32),
    ).validate()


# ---------------------------------------------------------------------------
# Scheduler policies (pure unit tests: no engine, no clocks).
# ---------------------------------------------------------------------------


def g_state(key, size, head_seq, wait_ticks=0, age_s=0.0):
    return GroupState(key=key, size=size, head_seq=head_seq,
                      head_wait_ticks=wait_ticks, head_age_s=age_s)


def test_fifo_picks_globally_oldest_group():
    groups = [g_state("a", size=8, head_seq=5),
              g_state("b", size=1, head_seq=2),
              g_state("c", size=3, head_seq=9)]
    assert FifoScheduler().select(groups, slots=4) == "b"


def test_occupancy_picks_fullest_group():
    groups = [g_state("a", size=2, head_seq=0),
              g_state("b", size=7, head_seq=3),
              g_state("c", size=4, head_seq=1)]
    assert OccupancyScheduler().select(groups, slots=8) == "b"


def test_occupancy_saturates_at_slots_and_breaks_ties_by_age():
    # Both a and b fill a 4-slot batch; a's head is older -> a wins.
    groups = [g_state("a", size=5, head_seq=1),
              g_state("b", size=20, head_seq=6),
              g_state("c", size=3, head_seq=0)]
    assert OccupancyScheduler().select(groups, slots=4) == "a"


def test_occupancy_starvation_override_by_ticks():
    groups = [g_state("hot", size=8, head_seq=50, wait_ticks=0),
              g_state("cold", size=1, head_seq=3, wait_ticks=4),
              g_state("colder", size=1, head_seq=1, wait_ticks=6)]
    sched = OccupancyScheduler(starvation_ticks=4)
    # Both cold groups are past the bound; the oldest head wins.
    assert sched.select(groups, slots=8) == "colder"


def test_occupancy_starvation_override_by_age():
    groups = [g_state("hot", size=8, head_seq=50),
              g_state("cold", size=1, head_seq=3, age_s=1.5)]
    sched = OccupancyScheduler(starvation_ticks=1000, starvation_age_s=1.0)
    assert sched.select(groups, slots=8) == "cold"


def test_make_scheduler_factory():
    assert make_scheduler("fifo").name == "fifo"
    assert make_scheduler("occupancy", starvation_ticks=5).starvation_ticks == 5
    custom = OccupancyScheduler()
    assert make_scheduler(custom) is custom
    with pytest.raises(ValueError):
        make_scheduler("lifo")
    with pytest.raises(ValueError):
        OccupancyScheduler(starvation_ticks=0)


# ---------------------------------------------------------------------------
# Heterogeneous catalog: bit-exactness and the trace bound.
# ---------------------------------------------------------------------------


def _catalog(key=0):
    """GCN+SAGE at f=5, GAT+GIN at f=12: 4 models, 2 feature dims."""
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    gcn = build_model("gcn", 5, 3, hidden=8)
    sage = build_model("sage", 5, 2, hidden=4)
    gat = build_model("gat", 12, 2, hidden=4, heads=2)
    gin = build_model("gin", 12, 2, hidden=8, mlp_layers=2)
    return {
        "gcn_f5": (gcn, gcn.init(ks[0]), "node", gcn_prepare),
        "sage_f5": (sage, sage.init(ks[1]), "node", None),
        "gat_f12": (gat, gat.init(ks[2]), "node", None),
        "gin_f12": (gin, gin.init(ks[3]), "graph", None),
    }


@pytest.mark.parametrize("scheduler", ["fifo", "occupancy"])
def test_multimodel_catalog_bit_exact(scheduler):
    catalog = _catalog()
    eng = GnnServeEngine(cfg=GhostConfig(v=8, n=8), slots=3,
                         scheduler=scheduler)
    for mid, (model, params, task, prep) in catalog.items():
        eng.register(mid, model, params, task=task, prepare_fn=prep)

    pool5 = [make_graph(s, f=5) for s in range(4)]
    pool12 = [make_graph(100 + s, f=12) for s in range(4)]
    requests = []
    for g5, g12 in zip(pool5, pool12):
        requests += [("gcn_f5", g5), ("gat_f12", g12),
                     ("sage_f5", g5), ("gin_f12", g12)]
    rep = eng.run(requests)

    assert rep.requests == len(requests)
    assert set(rep.per_model) == set(catalog)
    feat_dims = {catalog[mid][0].f_in for mid in catalog}
    assert len(feat_dims) >= 2 and len(catalog) >= 3

    rid = 0
    for g5, g12 in zip(pool5, pool12):
        for mid, g in (("gcn_f5", g5), ("gat_f12", g12),
                       ("sage_f5", g5), ("gin_f12", g12)):
            model, params, task, prep = catalog[mid]
            if prep is not None:
                g2, w = prep(g)
            else:
                g2, w = g, None
            pg = partition_graph(g2, v=8, n=8, edge_weights=w)
            featp = jnp.asarray(pg.pad_features(g.node_feat))
            # The reference is the *jitted* unbatched blocked forward —
            # what an unbatched deployment would actually run.  (Eager
            # execution can differ from any jitted run by 1 ULP in GAT's
            # fused softmax; that is an XLA property, not engine drift.)
            bgs = to_blocked(pg)
            ref = np.asarray(jax.jit(
                lambda p, f, m=model, bgs=bgs: m.apply_blocked(p, bgs, f)
            )(params, featp))
            if task == "node":
                ref = ref[: g.num_nodes]
            assert_ulp_close(eng.results[rid], ref,
                             err_msg=f"{mid} rid={rid}")
            rid += 1

    # One jit trace per (model, bucket): bounded by the observed product.
    distinct_buckets = len(rep.buckets)
    assert rep.traces_compiled == len(eng.pool)
    assert rep.traces_compiled <= len(catalog) * distinct_buckets


def test_models_share_preprocessing_across_catalog():
    """Two models with the same prepare transform share one partition."""
    g = make_graph(1, nv=20, ne=50, f=5)
    gcn = build_model("gcn", 5, 2, hidden=4)
    sage = build_model("sage", 5, 2, hidden=4)
    eng = GnnServeEngine(cfg=GhostConfig(v=8, n=8), slots=2)
    eng.register("gcn", gcn, gcn.init(jax.random.PRNGKey(0)))
    eng.register("sage", sage, sage.init(jax.random.PRNGKey(1)))
    eng.submit("gcn", g)
    eng.submit("sage", g)   # same structure, same (empty) salt -> cache hit
    eng.drain()
    assert eng.cache.stats.misses == 1
    assert eng.cache.stats.hits == 1


# ---------------------------------------------------------------------------
# Anti-starvation under sustained load.
# ---------------------------------------------------------------------------


def test_occupancy_antistarvation_serves_cold_group():
    """A lone cold request is served within the starvation bound even while
    a hot group stays permanently full."""
    hot = make_graph(2, nv=16, ne=40, f=5)
    cold = make_graph(3, nv=60, ne=150, f=5)   # different bucket
    model = build_model("gcn", 5, 2, hidden=4)
    params = model.init(jax.random.PRNGKey(0))
    bound = 3
    eng = GnnServeEngine(
        cfg=GhostConfig(v=8, n=8), slots=4,
        # age bound off: this tick-driven test wants the tick bound to be
        # what serves the cold group, deterministically.
        scheduler=OccupancyScheduler(starvation_ticks=bound,
                                     starvation_age_s=None))
    eng.register("m", model, params)

    cold_rid = eng.submit("m", cold)
    served_at = None
    for tick in range(10):
        for _ in range(4):
            eng.submit("m", hot)   # keep the hot group full every tick
        eng.step()
        if cold_rid in eng.results and served_at is None:
            served_at = tick
    assert served_at is not None, "cold request starved"
    assert served_at <= bound
    cold_rec = next(r for r in eng.records if r.rid == cold_rid)
    assert cold_rec.wait_ticks <= bound
    # Sanity: the hot group was indeed preferred before the bound hit.
    assert eng.records[0].rid != cold_rid


# ---------------------------------------------------------------------------
# Admission control.
# ---------------------------------------------------------------------------


def test_admission_reject_policy():
    g = make_graph(4, nv=12, ne=20, f=5)
    model = build_model("gcn", 5, 2, hidden=4)
    params = model.init(jax.random.PRNGKey(0))
    eng = GnnServeEngine(cfg=GhostConfig(v=8, n=8), slots=2, max_waiting=2)
    eng.register("m", model, params)
    assert eng.try_submit("m", g) == 0
    assert eng.try_submit("m", g) == 1
    assert eng.try_submit("m", g) is None   # queue full -> rejected
    with pytest.raises(QueueFullError):
        eng.submit("m", g)
    eng.drain()
    rep = eng.report(1.0)
    assert rep.requests == 2
    assert rep.admitted == 2 and rep.rejected == 2 and rep.shed == 0
    assert rep.reject_rate == pytest.approx(0.5)
    # Queue drained: the next submission is admitted again.
    assert eng.try_submit("m", g) is not None


def test_admission_shed_oldest_policy():
    g = make_graph(5, nv=12, ne=20, f=5)
    model = build_model("gcn", 5, 2, hidden=4)
    params = model.init(jax.random.PRNGKey(0))
    eng = GnnServeEngine(cfg=GhostConfig(v=8, n=8), slots=2, max_waiting=2,
                         admission_policy="shed-oldest")
    eng.register("m", model, params)
    r0 = eng.submit("m", g)
    r1 = eng.submit("m", g)
    r2 = eng.submit("m", g)   # sheds r0 to make room
    eng.drain()
    assert eng.shed_rids == [r0]
    assert r0 not in eng.results
    assert r1 in eng.results and r2 in eng.results
    rep = eng.report(1.0)
    assert rep.requests == 2
    assert rep.admitted == 3 and rep.shed == 1 and rep.rejected == 0


def test_run_interleaves_serving_with_bounded_queue():
    """Closed-loop run() makes progress instead of rejecting at the bound."""
    graphs = [make_graph(10 + s, nv=16, ne=30, f=5) for s in range(8)]
    model = build_model("gcn", 5, 2, hidden=4)
    params = model.init(jax.random.PRNGKey(0))
    eng = GnnServeEngine(cfg=GhostConfig(v=8, n=8), slots=2, max_waiting=2)
    eng.register("m", model, params)
    rep = eng.run(graphs)
    assert rep.requests == len(graphs)
    assert rep.rejected == 0
    assert len(eng.results) == len(graphs)


def test_shed_is_not_performed_when_preprocessing_fails():
    """A full queue must not lose a healthy victim to a doomed submission."""
    g = make_graph(6, nv=12, ne=20, f=5)
    model = build_model("gcn", 5, 2, hidden=4)
    params = model.init(jax.random.PRNGKey(0))
    eng = GnnServeEngine(cfg=GhostConfig(v=8, n=8), slots=2, max_waiting=2,
                         admission_policy="shed-oldest")
    eng.register("m", model, params)
    eng.submit("m", g)
    eng.submit("m", g)

    def boom(*a, **kw):
        raise RuntimeError("preprocessing exploded")

    eng.cache.get_or_partition = boom
    with pytest.raises(RuntimeError):
        eng.submit("m", g)
    # No victim shed, queue intact, and the failed admission rolled back.
    assert eng.shed_rids == []
    assert eng.num_waiting == 2
    assert eng.admission.stats.admitted == 2
    assert eng.admission.stats.shed == 0


def test_report_max_wait_sees_waiting_and_shed_requests():
    """The starvation gauge must not be blind to never-served requests."""
    hot = make_graph(7, nv=16, ne=40, f=5)
    cold = make_graph(8, nv=60, ne=150, f=5)   # different bucket
    model = build_model("gcn", 5, 2, hidden=4)
    params = model.init(jax.random.PRNGKey(0))
    eng = GnnServeEngine(
        cfg=GhostConfig(v=8, n=8), slots=4,
        # neither bound may trip: the point is the *gauge*, not a rescue
        scheduler=OccupancyScheduler(starvation_ticks=100,
                                     starvation_age_s=None))
    eng.register("m", model, params)
    cold_rid = eng.submit("m", cold)
    for _ in range(3):
        for _ in range(4):
            eng.submit("m", hot)
        eng.step()
    assert cold_rid not in eng.results          # still starving
    assert eng.report(1.0).max_wait_ticks >= 3  # ...and the gauge shows it

    # Shedding the starved request must keep its wait in the gauge too.
    eng2 = GnnServeEngine(cfg=GhostConfig(v=8, n=8), slots=2, max_waiting=2,
                          admission_policy="shed-oldest")
    eng2.register("m", model, params)
    eng2.submit("m", cold)
    eng2.submit("m", hot)
    eng2.step()                   # tick 1: serves one group
    eng2.submit("m", hot)
    eng2.submit("m", hot)         # queue full again -> sheds the oldest
    shed_wait = eng2.report(1.0).max_wait_ticks
    eng2.drain()
    assert eng2.shed_rids and shed_wait >= 1


def test_take_result_reclaims_memory():
    g = make_graph(9, nv=12, ne=20, f=5)
    model = build_model("gcn", 5, 2, hidden=4)
    params = model.init(jax.random.PRNGKey(0))
    eng = GnnServeEngine(cfg=GhostConfig(v=8, n=8), slots=2)
    eng.register("m", model, params)
    rid = eng.submit("m", g)
    eng.drain()
    out = eng.take_result(rid)
    assert out.shape[0] == g.num_nodes
    assert rid not in eng.results
    with pytest.raises(KeyError):
        eng.take_result(rid)


# ---------------------------------------------------------------------------
# Backend fallbacks and degenerate graphs through the catalog path.
# ---------------------------------------------------------------------------


class _MaxPoolModel:
    """Minimal MAX-reduce node model: aggregate(MAX) then a linear head.

    MAX has no Pallas SpMM analogue (the optical comparator is not an
    MXU contraction), so inside a Pallas-backend executor this must take
    the jnp fallback path of aggregate_blocked.
    """

    f_in = 6

    def init(self, key):
        return {"w": jax.random.normal(key, (self.f_in, 3), jnp.float32)}

    def apply_blocked(self, params, bg, feat_padded, quantized=False):
        h = aggregate_blocked(bg, feat_padded, ReduceOp.MAX)
        return h @ params["w"]


def test_max_reduce_model_uses_jnp_fallback_in_pallas_executor():
    graphs = [make_graph(20 + s, nv=14, ne=30, f=6) for s in range(3)]
    model = _MaxPoolModel()
    params = model.init(jax.random.PRNGKey(7))
    eng = GnnServeEngine(cfg=GhostConfig(v=4, n=4), slots=2,
                         backend="pallas")
    eng.register("maxpool", model, params, task="node")
    eng.run(graphs)
    for i, g in enumerate(graphs):
        pg = partition_graph(g, v=4, n=4)
        featp = jnp.asarray(pg.pad_features(g.node_feat))
        with aggregate_backend("pallas"):
            ref = np.asarray(model.apply_blocked(params, to_blocked(pg),
                                                 featp))[: g.num_nodes]
        np.testing.assert_array_equal(eng.results[i], ref)


def test_zero_edge_graphs_through_multimodel_engine():
    rng = np.random.default_rng(0)
    z5 = Graph(edge_src=np.zeros(0, np.int32), edge_dst=np.zeros(0, np.int32),
               node_feat=rng.standard_normal((7, 5)).astype(np.float32)
               ).validate()
    z12 = Graph(edge_src=np.zeros(0, np.int32),
                edge_dst=np.zeros(0, np.int32),
                node_feat=rng.standard_normal((9, 12)).astype(np.float32)
                ).validate()
    gcn = build_model("gcn", 5, 2, hidden=4)
    gin = build_model("gin", 12, 2, hidden=4, mlp_layers=2)
    eng = GnnServeEngine(cfg=GhostConfig(v=4, n=4), slots=2,
                         backend="pallas")
    eng.register("gcn", gcn, gcn.init(jax.random.PRNGKey(0)))
    eng.register("gin", gin, gin.init(jax.random.PRNGKey(1)), task="graph")
    rep = eng.run([("gcn", z5), ("gin", z12)])
    assert rep.requests == 2
    for mid, g, rid, task in (("gcn", z5, 0, "node"), ("gin", z12, 1, "graph")):
        model = {"gcn": gcn, "gin": gin}[mid]
        params = eng.registry[mid].params
        pg = partition_graph(g, v=4, n=4)
        featp = jnp.asarray(pg.pad_features(g.node_feat))
        with aggregate_backend("pallas"):
            ref = np.asarray(model.apply_blocked(params, to_blocked(pg),
                                                 featp))
        if task == "node":
            ref = ref[: g.num_nodes]
        np.testing.assert_array_equal(eng.results[rid], ref)
