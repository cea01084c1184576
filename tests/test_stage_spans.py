"""Stage budget of a served request, and its spans on the profiler's clock.

The load-bearing claims:
  * every served record carries the stage fields (``prep_s``,
    ``stack_s``, ``device_wait_s``, ``h2d_s``, ``h2d_bytes``, ``run_s``,
    ``publish_s``, ``pickup_s``), none negative;
  * the stages are contiguous: ``wait_s + stack_s + device_wait_s +
    h2d_s + run_s + publish_s + pickup_s`` is the request's submit to
    pickup, with ``sample_s`` and ``prep_s`` inside ``wait_s``;
  * ``h2d_bytes`` is what the executor handed to ``device_put``: the
    batch's filled slots (one-device pools copy in no empty slot);
  * a ``jax.profiler`` trace holds one ``gnn.<stage>`` host event per
    stage, with ``rid`` on request stages and ``batch``/``device`` on
    batch stages; a router adds ``gnn.route``.

Both the serial loop (``pipeline_depth=0``) and the pipelined one (2)
serve whole-graph requests and node queries here.
"""

import collections
import glob
import sys
import threading
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.graph import Graph
from repro.gnn import build_model
from repro.photonic.perf import GhostConfig
from repro.serving import EngineRouter, GnnServeEngine, HostGraph

CFG = GhostConfig(v=8, n=8)
F = 6
SLOTS = 2
STAGES = ("wait_s", "stack_s", "device_wait_s", "h2d_s", "run_s",
          "publish_s", "pickup_s")
BATCH_FIELDS = ("stack_s", "device_wait_s", "h2d_s", "h2d_bytes", "run_s",
                "publish_s")
CASES = [(depth, kind) for depth in (0, 2) for kind in ("graph", "nodes")]
CASE_IDS = [f"depth{d}-{k}" for d, k in CASES]


def make_graph(seed, nv=14, ne=40):
    rng = np.random.default_rng(seed)
    return Graph(
        edge_src=rng.integers(0, nv, ne).astype(np.int32),
        edge_dst=rng.integers(0, nv, ne).astype(np.int32),
        node_feat=rng.standard_normal((nv, F)).astype(np.float32),
    ).validate()


def register(server, kind, **kwargs):
    arch = "gcn" if kind == "graph" else "sage"
    model = build_model(arch, F, 3, hidden=4)
    server.register("m", model, model.init(jax.random.PRNGKey(0)), **kwargs)
    if kind == "nodes":
        host = HostGraph.synthetic_power_law(200, avg_degree=4,
                                             num_features=F, seed=0)
        server.register_host_graph("hg", host, fanouts=(3, 2))


def submit(server, kind, i):
    if kind == "graph":
        return server.submit("m", make_graph(i))
    return server.submit_nodes("m", [i])


def serve(depth, kind, n=5):
    """Submit ``n`` requests before the loop starts, let the loop serve
    them all, then pick each up.  Nothing else runs at submit or pickup,
    so the client's stamps bracket the engine's own closely.  Returns the
    engine and, per rid, the client's submit-to-pickup seconds."""
    eng = GnnServeEngine(cfg=CFG, slots=SLOTS, pipeline_depth=depth)
    register(eng, kind)
    sent = {}
    for i in range(n):
        t = time.perf_counter()
        sent[submit(eng, kind, i)] = t
    eng.start()
    eng.drain()
    client_s = {}
    for rid, t in sent.items():
        eng.result(rid, timeout=60)
        client_s[rid] = time.perf_counter() - t
    eng.stop()
    return eng, client_s


def stage_sum(rec):
    return sum(getattr(rec, f) for f in STAGES)


@pytest.mark.parametrize("depth,kind", CASES, ids=CASE_IDS)
def test_stage_fields_are_nonnegative(depth, kind):
    eng, _ = serve(depth, kind)
    assert len(eng.records) == 5
    for rec in eng.records:
        for f in (*STAGES, "prep_s", "sample_s", "h2d_bytes"):
            assert getattr(rec, f) >= 0, (f, rec)
        assert rec.h2d_bytes > 0 and rec.batch >= 0
        assert rec.prep_s <= rec.wait_s
        assert rec.sample_s + rec.prep_s <= rec.wait_s
        assert (rec.sample_s > 0) == (kind == "nodes")
    # The batch stages are one value per batch, on each of its records.
    by_batch = collections.defaultdict(set)
    for rec in eng.records:
        by_batch[rec.batch].add(tuple(getattr(rec, f) for f in BATCH_FIELDS))
    assert len(by_batch) == 3  # 5 requests in batches of 2
    assert all(len(v) == 1 for v in by_batch.values())


@pytest.mark.parametrize("depth,kind", CASES, ids=CASE_IDS)
def test_stage_budget_closes(depth, kind):
    eng, client_s = serve(depth, kind)
    for rec in eng.records:
        # Up to the output on the host the stages are the latency.
        assert stage_sum(rec) - rec.publish_s - rec.pickup_s == \
            pytest.approx(rec.latency_s, abs=1e-6)
        # And with publish and pickup, the client's submit to pickup.
        assert abs(stage_sum(rec) - client_s[rec.rid]) <= 1e-3, rec
        assert stage_sum(rec) <= client_s[rec.rid]


@pytest.mark.parametrize("depth,kind", CASES, ids=CASE_IDS)
def test_h2d_bytes_counts_the_stacked_arrays(depth, kind):
    eng = GnnServeEngine(cfg=CFG, slots=SLOTS, pipeline_depth=depth)
    register(eng, kind)
    handed = []
    device_args = eng.pool.device_args

    def spy(entry, *batch, filled):
        assert all(a.shape[0] == SLOTS for a in batch)
        # The pool has one device: it copies in the filled slots alone.
        handed.append(sum(a[:filled].nbytes for a in batch))
        return device_args(entry, *batch, filled=filled)

    eng.pool.device_args = spy
    eng.start()
    rids = [submit(eng, kind, i) for i in range(3)]
    for rid in rids:
        eng.result(rid, timeout=60)
    eng.stop()
    per_batch = {rec.batch: rec.h2d_bytes for rec in eng.records}
    assert sorted(per_batch.values()) == sorted(handed)


def trace_events(log_dir):
    """(name, stats) of every ``gnn.*`` host event of the trace."""
    path = sorted(glob.glob(str(log_dir / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events += [(e.name, dict(e.stats)) for e in line.events
                       if e.name.startswith("gnn.")]
    return events


@pytest.mark.parametrize("depth", [0, 2])
def test_profiler_trace_names_every_stage(depth, tmp_path):
    eng = GnnServeEngine(cfg=CFG, slots=SLOTS, pipeline_depth=depth)
    register(eng, "nodes")
    eng.start()
    eng.result(eng.submit_nodes("m", [0]), timeout=60)  # compile first
    jax.profiler.start_trace(str(tmp_path))
    try:
        rids = [eng.submit_nodes("m", [v]) for v in (1, 2)]
        for rid in rids:
            eng.result(rid, timeout=60)
    finally:
        jax.profiler.stop_trace()
    eng.stop()
    events = trace_events(tmp_path)
    names = {n for n, _ in events}
    assert {"gnn.sample", "gnn.prep", "gnn.stack", "gnn.h2d", "gnn.run",
            "gnn.publish"} <= names
    batches = {r.batch for r in eng.records if r.rid in rids}
    for name, stats in events:
        if name in ("gnn.sample", "gnn.prep"):
            assert stats["rid"] in rids
        else:
            assert stats["batch"] in batches
            assert stats["device"] == eng._device_id


@pytest.mark.parametrize("kind", ["graph", "nodes"])
def test_router_replicas_carry_stages_and_route_span(kind, tmp_path):
    router = EngineRouter(2, slots=SLOTS, cfg=CFG)
    register(router, kind, hot=True)
    router.start()
    jax.profiler.start_trace(str(tmp_path))
    try:
        # Submit a batch' worth to each replica before picking any up, so
        # routing by backlog spreads them.
        rids = [submit(router, kind, i) for i in range(2 * SLOTS + 2)]
        for rid in rids:
            router.result(rid, timeout=60)
    finally:
        jax.profiler.stop_trace()
    router.stop()
    assert all(e.records for e in router.replicas)
    for e in router.replicas:
        for rec in e.records:
            assert rec.h2d_bytes > 0 and rec.pickup_s is not None
            assert all(getattr(rec, f) >= 0 for f in STAGES)
    assert "gnn.route" in {n for n, _ in trace_events(tmp_path)}


def test_pickup_stamps_survive_a_submit_storm():
    """Many clients submit and pick up against the pipelined loop while
    the interpreter switches threads often: every record gets its own
    pickup stamp once, and no index entry outlives its result."""
    eng = GnnServeEngine(cfg=CFG, slots=SLOTS, pipeline_depth=2)
    register(eng, "graph")
    eng.start()
    errors = []

    def client(k):
        try:
            for i in range(6):
                eng.result(eng.submit("m", make_graph(10 * k + i)),
                           timeout=60)
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert not errors
    # Left unpicked, then reset: the index goes with the results.
    eng.submit("m", make_graph(99))
    eng.stop()
    assert len(eng._unpicked) == 1
    eng.reset_metrics()
    assert not eng._unpicked and not eng.records


def test_every_answer_of_the_storm_is_stamped():
    eng = GnnServeEngine(cfg=CFG, slots=SLOTS, pipeline_depth=2)
    register(eng, "nodes")
    eng.start()
    rids = []
    lock = threading.Lock()

    def client(k):
        for i in range(5):
            rid = eng.submit_nodes("m", [7 * k + i])
            with lock:
                rids.append(rid)
            eng.result(rid, timeout=60)

    threads = [threading.Thread(target=client, args=(k,)) for k in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    eng.stop()
    assert sorted(r.rid for r in eng.records) == sorted(rids)
    assert not eng._unpicked
    for rec in eng.records:
        assert rec.pickup_s is not None and rec.pickup_s >= 0
        assert stage_sum(rec) - rec.publish_s - rec.pickup_s == \
            pytest.approx(rec.latency_s, abs=1e-6)
