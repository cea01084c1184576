"""Replica router (serving.router.EngineRouter).

The load-bearing claims:
  * catalog-aware placement — hot models land on every replica, cold
    models pin to exactly one (least-loaded, or an explicit pin);
  * load-aware routing with per-replica admission fallback: a rejection on
    the shortest queue fails over to the next eligible replica before
    surfacing;
  * global rids round-trip through ``take_result`` to the owning replica;
  * the merged report equals what one engine would say about the union
    stream, plus per-replica served counts.

No devices beyond the default are needed — replicas are plain engines.
"""

import jax
import numpy as np
import pytest

from repro.core import Graph
from repro.gnn import build_model
from repro.serving import EngineRouter, GnnServeEngine, QueueFullError


def make_graph(seed, nv=30, ne=100, f=8):
    rng = np.random.default_rng(seed)
    return Graph(
        edge_src=rng.integers(0, nv, ne).astype(np.int32),
        edge_dst=rng.integers(0, nv, ne).astype(np.int32),
        node_feat=rng.standard_normal((nv, f)).astype(np.float32),
    ).validate()


def make_model(classes=3, seed=0):
    model = build_model("gcn", 8, classes, hidden=8)
    return model, model.init(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# Placement.
# ---------------------------------------------------------------------------


def test_hot_model_registers_everywhere():
    model, params = make_model()
    router = EngineRouter(3, slots=2)
    assert router.register("m", model, params, hot=True) == (0, 1, 2)
    for e in router.replicas:
        assert "m" in e.registry


def test_cold_models_balance_across_replicas():
    model, params = make_model()
    router = EngineRouter(2, slots=2)
    homes = [router.register(f"m{i}", model, params) for i in range(4)]
    assert all(len(h) == 1 for h in homes)
    # Least-loaded placement alternates 0,1,0,1.
    assert [h[0] for h in homes] == [0, 1, 0, 1]
    assert router.placement("m2") == (0,)


def test_explicit_pin_and_errors():
    model, params = make_model()
    router = EngineRouter(2, slots=2)
    assert router.register("m", model, params, replica=1) == (1,)
    with pytest.raises(ValueError, match="already placed"):
        router.register("m", model, params)
    with pytest.raises(ValueError, match="replica"):
        router.register("m2", model, params, hot=True, replica=0)
    with pytest.raises(ValueError, match="out of range"):
        router.register("m3", model, params, replica=5)
    with pytest.raises(KeyError, match="unknown model_id"):
        router.placement("nope")
    with pytest.raises(ValueError, match="num_replicas"):
        EngineRouter(0)


# ---------------------------------------------------------------------------
# Routing + admission fallback.
# ---------------------------------------------------------------------------


def test_routes_to_shortest_queue():
    model, params = make_model()
    router = EngineRouter(2, slots=2)
    router.register("m", model, params, hot=True)
    g = make_graph(0)
    router.submit("m", g)
    router.submit("m", g)
    # Without serving, two submissions must land on different replicas.
    assert [e.num_waiting for e in router.replicas] == [1, 1]


def test_routes_by_estimated_slack_not_raw_queue_length():
    """Two replicas with equal-length queues are NOT equally loaded when
    their learned service times differ: routing must prefer the smaller
    time backlog (queued batches x expected service), falling back to raw
    queue length only on ties (the cold-start behavior above)."""
    model, params = make_model()
    router = EngineRouter(2, slots=4)
    router.register("m", model, params, hot=True)
    g = make_graph(3)
    router.submit("m", g)   # cold: ties -> replica 0
    router.submit("m", g)   # cold: queue tie-break -> replica 1
    r0, r1 = router.replicas
    assert [r0.num_waiting, r1.num_waiting] == [1, 1]
    # Inject asymmetric learned service times for the one waiting group
    # (the EWMA the engines would learn from serving: replica 0 fast,
    # replica 1 slow — e.g. different device health or catalog pressure).
    (key,) = r0._groups
    r0._service_ewma[key] = 0.001   # 1 ms batches
    r1._service_ewma[key] = 0.100   # 100 ms batches
    # Every new request now lands on replica 0 — its queue grows LONGER
    # than replica 1's, yet its estimated backlog time stays smaller.
    for _ in range(3):
        router.submit("m", g)
    assert r0.num_waiting == 4      # 1 batch x 1 ms  << 1 batch x 100 ms
    assert r1.num_waiting == 1
    backlog0, _ = r0.queue_pressure()
    backlog1, _ = r1.queue_pressure()
    assert backlog0 < backlog1
    assert router.drain() == 5
    # The merged report surfaces the per-replica models it routed by.
    rep = router.report(1.0)
    assert rep.service_time_ms      # cross-replica mean per key
    assert rep.replicas["replica0"]["service_time_ms"]
    assert rep.replicas["replica1"]["service_time_ms"]


def test_admission_fallback_across_replicas():
    model, params = make_model()
    router = EngineRouter(2, slots=2, max_waiting=1,
                          admission_policy="reject")
    router.register("m", model, params, hot=True)
    g = make_graph(1)
    assert router.try_submit("m", g) is not None   # shortest queue: A
    assert router.try_submit("m", g) is not None   # A full -> lands on B
    assert router.try_submit("m", g) is None       # both full: tried A AND B
    with pytest.raises(QueueFullError):
        router.submit("m", g)
    # The failed attempts rejected on every eligible replica (fallback ran).
    total_rejected = sum(e.admission.stats.rejected
                        for e in router.replicas)
    assert total_rejected >= 2
    assert router.drain() == 2


def test_cold_traffic_stays_on_pinned_replica():
    model, params = make_model()
    router = EngineRouter(2, slots=2, max_waiting=1,
                          admission_policy="reject")
    home = router.register("cold", model, params)[0]
    g = make_graph(2)
    assert router.try_submit("cold", g) is not None
    # The pinned replica is full and there is no fallback target.
    assert router.try_submit("cold", g) is None
    router.drain()
    other = router.replicas[1 - home]
    assert not other.records


# ---------------------------------------------------------------------------
# Results + merged report.
# ---------------------------------------------------------------------------


def test_results_round_trip_matches_single_engine():
    model, params = make_model()
    graphs = [make_graph(s) for s in range(6)]

    router = EngineRouter(2, slots=2)
    router.register("m", model, params, hot=True)
    rids = [router.submit("m", g) for g in graphs]
    router.drain()

    single = GnnServeEngine(slots=2)
    single.register("m", model, params)
    srids = [single.submit("m", g) for g in graphs]
    single.drain()

    for rid, srid in zip(rids, srids):
        np.testing.assert_array_equal(router.take_result(rid),
                                      single.take_result(srid))
    with pytest.raises(KeyError):
        router.take_result(rids[0])  # already taken


def test_merged_report():
    hot_model, hot_params = make_model(3, seed=0)
    cold_model, cold_params = make_model(2, seed=1)
    router = EngineRouter(2, slots=2)
    router.register("hot", hot_model, hot_params, hot=True)
    cold_home = router.register("cold", cold_model, cold_params)[0]

    stream = ([("hot", make_graph(100 + i)) for i in range(6)]
              + [("cold", make_graph(200 + i)) for i in range(3)])
    rep = router.run(stream)

    assert rep.requests == 9
    assert rep.per_model == {"hot": 6, "cold": 3}
    assert rep.admitted == 9 and rep.rejected == 0
    assert set(rep.replicas) == {"replica0", "replica1"}
    assert sum(info["served"] for info in rep.replicas.values()) == 9
    # Cold traffic shows up only under its pinned replica.
    for name, info in rep.replicas.items():
        if name != f"replica{cold_home}":
            assert "cold" not in info["per_model"]
    assert rep.traces_compiled == sum(
        info["traces_compiled"] for info in rep.replicas.values())
    assert "replicas:" in rep.pretty()


def test_merged_report_unions_replica_views():
    """Regression: the merged report must not take replica 0's kernel
    configs / topology as the whole story — replicas with distinct meshes
    or overrides keep their contributions in the union."""
    from types import SimpleNamespace

    from repro.launch.mesh import make_data_mesh

    model, params = make_model()
    # Distinct meshes per replica: replica0 meshless, replica1 on a 1-device
    # data mesh (no extra host devices needed).
    router = EngineRouter(2, slots=2, meshes=[None, make_data_mesh(1)])
    router.register("m", model, params, hot=True)
    # Distinct per-replica kernel-config views (a per-replica override).
    cfg0 = SimpleNamespace(fused=True, tile=8)
    cfg1 = SimpleNamespace(fused=False, tile=4)
    router.replicas[0].pool.kernel_config = cfg0
    router.replicas[1].pool.kernel_config = cfg1
    rep = router.run([("m", make_graph(300 + i)) for i in range(4)])

    # Replica 1's mesh is not dropped: the merged topology aggregates.
    assert rep.topology["num_devices"] == 2
    assert rep.topology["heterogeneous"] is True
    assert rep.topology["mesh_shapes"]["replica1"] == {"data": 1}
    # Conflicting "*" overrides both survive, replica detail preserved.
    assert rep.kernel_configs["*"] == vars(cfg0)
    assert rep.kernel_configs["replica1:*"] == vars(cfg1)
    assert rep.replicas["replica0"]["kernel_configs"]["*"] == vars(cfg0)
    assert rep.replicas["replica1"]["kernel_configs"]["*"] == vars(cfg1)
    assert rep.replicas["replica1"]["topology"]["num_devices"] == 1
    # Uniform replicas still report the shared view unchanged.  Explicitly
    # meshless: without meshes= a host with several devices pins replica i
    # to device i, and the merged topology would list those 1-device meshes.
    router2 = EngineRouter(2, slots=2, meshes=[None, None])
    router2.register("m", model, params, hot=True)
    rep2 = router2.run([("m", make_graph(400))])
    assert rep2.topology == {}
    assert rep2.kernel_configs == {}


# ---------------------------------------------------------------------------
# Node-query routing.
# ---------------------------------------------------------------------------


def test_node_queries_route_to_host_graph_holders():
    from repro.serving import HostGraph

    host = HostGraph.synthetic_power_law(300, avg_degree=5, num_features=8,
                                         seed=0)
    model = build_model("sage", 8, 2, hidden=8)
    params = model.init(jax.random.PRNGKey(0))
    router = EngineRouter(2, slots=2)
    router.register("m", model, params, hot=True)
    # Host graph pinned to replica 1: queries must land there even though
    # the model is hot everywhere.
    assert router.register_host_graph("hg", host, replicas=[1],
                                      fanouts=(4, 4)) == (1,)
    rids = [router.submit_nodes("m", [i]) for i in range(4)]
    router.drain()
    assert len(router.replicas[1].records) == 4
    assert not router.replicas[0].records
    for rid in rids:
        assert router.take_result(rid).shape == (1, 2)
    # Placement bookkeeping + error paths.
    assert router.host_placement("hg") == (1,)
    with pytest.raises(ValueError, match="already placed"):
        router.register_host_graph("hg", host)
    with pytest.raises(KeyError, match="unknown host graph"):
        router.host_placement("nope")
    with pytest.raises(ValueError, match="out of range"):
        router.register_host_graph("hg2", host, replicas=[5])


def test_node_queries_balance_and_intersect_placement():
    from repro.serving import HostGraph

    host = HostGraph.synthetic_power_law(200, avg_degree=4, num_features=8,
                                         seed=1)
    model = build_model("sage", 8, 2, hidden=8)
    params = model.init(jax.random.PRNGKey(0))
    router = EngineRouter(2, slots=2)
    cold_home = router.register("cold", model, params)[0]
    router.register_host_graph("hg", host)  # every replica holds it
    # Eligible = model placement ∩ host placement = the cold pin.
    router.submit_nodes("cold", [3])
    router.submit_nodes("cold", [4])
    assert router.replicas[cold_home].num_waiting == 2
    assert router.replicas[1 - cold_home].num_waiting == 0
    router.drain()
    # Empty intersection raises rather than silently serving elsewhere.
    model2 = build_model("sage", 8, 2, hidden=8)
    router2 = EngineRouter(2, slots=2)
    router2.register("m", model2, model2.init(jax.random.PRNGKey(1)),
                     replica=0)
    router2.register_host_graph("hg", host, replicas=[1])
    with pytest.raises(ValueError, match="no replica holds both"):
        router2.try_submit_nodes("m", [0])


def test_router_bare_graph_single_model():
    model, params = make_model()
    router = EngineRouter(2, slots=2)
    router.register("m", model, params, hot=True)
    rep = router.run([make_graph(7)])
    assert rep.requests == 1


def test_meshes_length_validation():
    with pytest.raises(ValueError, match="meshes"):
        EngineRouter(2, meshes=[None])
    with pytest.raises(ValueError, match="not both"):
        EngineRouter(1, meshes=[None], mesh=None)


def test_replicas_spread_over_visible_devices():
    """Without meshes, replica i runs on visible device i (round robin):
    every output it serves is computed there."""
    devices = jax.devices()
    model, params = make_model()
    router = EngineRouter(3, slots=2)
    router.register("m", model, params, hot=True)
    for i in range(6):
        router.submit("m", make_graph(500 + i))
    router.drain()
    for i, replica in enumerate(router.replicas):
        want = (devices[i % len(devices)].id,)
        assert {r.devices for r in replica.records} == {want}
        assert router.report(1.0).replicas[f"replica{i}"]["devices"] == \
            list(want)
