"""Reused host stacking buffers.

``GnnServeEngine._stack`` fills a buffer set taken from an engine-owned
free list instead of zero-filling fresh arrays for every batch.  The
claims held here:
  * every stacked batch is byte-equal to a fresh zero-filled stack, empty
    slots zero in all four arrays, through refills of fewer and more
    requests and a switch of bucket and back;
  * served answers are bit-identical to a fresh engine's;
  * a set is never handed out while its batch is un-run;
  * the free list holds at most ``2 * pipeline_depth + 1`` sets and drops
    the least recently returned one;
  * a run that raises returns its set;
  * ``RequestRecord.stack_reused``, the ``gnn.stack`` span's ``reused``
    and ``pipeline_stats()`` agree, for an engine and summed over router
    replicas.
"""

import threading
import time

import jax
import numpy as np
import pytest

from repro.core.graph import Graph
from repro.gnn import build_model
from repro.photonic.perf import GhostConfig
from repro.serving import EngineRouter, GnnServeEngine
from repro.serving.engine import _StackBuffers, _StackFreeList
from test_stage_spans import trace_events

CFG = GhostConfig(v=8, n=8)
SLOTS = 4
SMALL, LARGE = 12, 40   # vertices: two buckets of different shapes


def make_graph(seed, nv, ne=None, f=5):
    rng = np.random.default_rng(seed)
    ne = ne or 3 * nv
    return Graph(
        edge_src=rng.integers(0, nv, ne).astype(np.int32),
        edge_dst=rng.integers(0, nv, ne).astype(np.int32),
        node_feat=rng.standard_normal((nv, f)).astype(np.float32),
    ).validate()


def make_engine(depth=0, **kwargs):
    model = build_model("gcn", 5, 2, hidden=4)
    eng = GnnServeEngine(cfg=CFG, slots=SLOTS, pipeline_depth=depth,
                         **kwargs)
    eng.register("m", model, model.init(jax.random.PRNGKey(0)))
    return eng


# Batches of 4, 1 and 3 requests in one bucket, a switch of bucket, and
# back: each refill leaves fewer or more slots empty than the last.
SEQUENCE = [(SMALL, 4), (SMALL, 1), (SMALL, 3), (LARGE, 2), (SMALL, 2)]


def graphs_of(sequence):
    seed = 0
    out = []
    for nv, k in sequence:
        out.append([make_graph(seed + i, nv) for i in range(k)])
        seed += k
    return out


def fresh_stack(slots, bucket, batch):
    """The stack as built before buffers were reused: fresh zeros, then
    each request's padded tiles and features."""
    blocks = np.zeros((slots, bucket.num_blocks, bucket.v, bucket.n),
                      np.float32)
    rows = np.zeros((slots, bucket.num_blocks), np.int32)
    cols = np.zeros((slots, bucket.num_blocks), np.int32)
    feats = np.zeros((slots, bucket.padded_src, bucket.f), np.float32)
    for i, p in enumerate(batch):
        blocks[i], rows[i], cols[i] = p.blocks, p.block_row, p.block_col
        feats[i] = p.feat
    return blocks, rows, cols, feats


def spy_stack(eng):
    """Wrap ``eng._stack``; returns the list of stacked batches it saw,
    each with a copy of its arrays and the fresh stack it must equal."""
    seen = []
    real = eng._stack

    def stack(*args):
        sb = real(*args)
        seen.append((sb, [a.copy() for a in sb.bufs.arrays],
                     fresh_stack(eng.slots, sb.key[1], sb.batch)))
        return sb

    eng._stack = stack
    return seen


@pytest.mark.parametrize("depth,reused", [
    # Depth 0 keeps one set, so the switch of bucket drops the small one.
    (0, [False, True, True, False, False]),
    (2, [False, True, True, False, True]),
])
def test_refills_equal_a_fresh_zero_filled_stack(depth, reused):
    eng = make_engine(depth)
    seen = spy_stack(eng)
    for batch in graphs_of(SEQUENCE):
        for g in batch:
            eng.submit("m", g)
        assert eng.step() == len(batch)
    assert len({sb.key for sb, _, _ in seen}) == 2
    assert [sb.reused for sb, _, _ in seen] == reused
    for (sb, got, want), (_, k) in zip(seen, SEQUENCE):
        assert len(sb.batch) == k
        for g, w in zip(got, want):
            assert g.dtype == w.dtype and g.shape == w.shape
            assert g.tobytes() == w.tobytes()
            assert not g[k:].any()   # empty slots are zero


def test_served_answers_equal_a_fresh_engine():
    batches = graphs_of(SEQUENCE)
    eng = make_engine(2)
    served = []
    for batch in batches:
        rids = [eng.submit("m", g) for g in batch]
        eng.step()
        served.append([eng.take_result(r) for r in rids])
    assert eng.pipeline_stats()["stack_sets_reused"] == 3
    for batch, outs in zip(batches, served):
        fresh = make_engine(2)
        rids = [fresh.submit("m", g) for g in batch]
        fresh.step()
        assert fresh.pipeline_stats()["stack_sets_reused"] == 0
        for r, out in zip(rids, outs):
            np.testing.assert_array_equal(fresh.take_result(r), out)


def test_a_set_is_never_handed_out_while_its_batch_is_unrun():
    eng = make_engine(2)
    in_use, lock = set(), threading.Lock()
    current = threading.local()
    snapshots = {}
    real_stack, real_run = eng._stack, eng._run_stacked
    real_give = eng._stack_free.give
    device_args = eng.pool.device_args

    def stack(*args):
        sb = real_stack(*args)
        with lock:
            assert id(sb.bufs) not in in_use
            in_use.add(id(sb.bufs))
        snapshots[id(sb)] = (sb, [a.copy() for a in sb.bufs.arrays])
        return sb

    def give(bufs):
        with lock:
            in_use.discard(id(bufs))
        real_give(bufs)

    def run(sb):
        current.sb = sb
        return real_run(sb)

    def slow_device_args(entry, *batch, **kwargs):
        time.sleep(0.01)   # let the stacker run ahead
        _, want = snapshots[id(current.sb)]
        for got, w in zip(batch, want):
            np.testing.assert_array_equal(got, w)
        return device_args(entry, *batch, **kwargs)

    eng._stack, eng._run_stacked = stack, run
    eng._stack_free.give = give
    eng.pool.device_args = slow_device_args
    eng.start()
    rids = [eng.submit("m", make_graph(i, SMALL)) for i in range(60)]
    for rid in rids:
        eng.result(rid, timeout=120)
    eng.stop()
    assert len(eng.records) == 60
    # One shape, and at most 2 * depth + 1 sets in flight at once: the 15
    # or more batches of 60 requests allocate no more sets than that.
    stats = eng.pipeline_stats()
    assert stats["stack_sets_allocated"] <= 2 * eng.pipeline_depth + 1
    assert stats["stack_sets_reused"] >= 15 - stats["stack_sets_allocated"]


@pytest.mark.parametrize("depth", [0, 1, 2])
def test_free_list_is_capped_by_pipeline_depth(depth):
    eng = make_engine(depth)
    cap = 2 * depth + 1
    assert eng._stack_free.cap == cap
    # Many buckets (different feature widths) each leave a set behind.
    for i, nv in enumerate((8, 12, 20, 40, 70, 130)):
        eng.submit("m", make_graph(i, nv))
        eng.step()
        assert len(eng._stack_free._sets) <= cap
    assert len(eng._stack_free._sets) == cap


def test_free_list_drops_the_least_recently_returned_set():
    free = _StackFreeList(3)
    a, b = (1, 2, 1, 1, 1, 1), (1, 4, 1, 1, 1, 1)
    sets = [_StackBuffers(a), _StackBuffers(b), _StackBuffers(a),
            _StackBuffers(b)]
    for s in sets:
        free.give(s)
    assert len(free._sets) == 3                 # sets[0] dropped
    assert free.take(a) is sets[2]
    assert free.take(a) is None
    free.give(sets[2])                    # now b, b, a: take b, newest
    assert free.take(b) is sets[3]
    assert free.take(b) is sets[1]
    assert free.take((1, 8, 1, 1, 1, 1)) is None


def test_a_failed_run_returns_its_set():
    eng = make_engine(0)
    seen = spy_stack(eng)

    def boom(*a, **kw):
        raise RuntimeError("device_put failed")

    eng.pool.device_args = boom
    eng.submit("m", make_graph(0, SMALL))
    with pytest.raises(RuntimeError, match="device_put failed"):
        eng.step()
    (sb, _, _), = seen
    assert len(eng._stack_free._sets) == 1
    assert eng._stack_free.take(sb.bufs.shape) is sb.bufs


def reused_batches(records):
    per_batch = {r.batch: r.stack_reused for r in records}
    return sum(per_batch.values()), len(per_batch)


def test_records_and_pipeline_stats_agree():
    eng = make_engine(2)
    eng.start()
    # Two waves: the first wave's sets are all back on the free list
    # before the second is submitted, so the second must reuse some (in
    # one wave every batch could be in flight before any set returns).
    for wave in (range(10), range(10, 20)):
        rids = [eng.submit("m", make_graph(i, SMALL if i % 4 else LARGE))
                for i in wave]
        for rid in rids:
            eng.result(rid, timeout=120)
    eng.stop()
    reused, batches = reused_batches(eng.records)
    stats = eng.pipeline_stats()
    assert stats["stack_sets_reused"] == reused > 0
    assert stats["stack_sets_allocated"] == batches - reused > 0
    assert eng.report(1.0).pipeline["stack_sets_reused"] == reused
    eng.reset_metrics()
    stats = eng.pipeline_stats()
    assert stats["stack_sets_reused"] == stats["stack_sets_allocated"] == 0


def test_router_sums_replica_counters():
    model = build_model("gcn", 5, 2, hidden=4)
    router = EngineRouter(2, slots=2, cfg=CFG)
    router.register("m", model, model.init(jax.random.PRNGKey(0)), hot=True)
    router.start()
    rids = [router.submit("m", make_graph(i, SMALL)) for i in range(12)]
    for rid in rids:
        router.result(rid, timeout=120)
    router.stop()
    counts = [reused_batches(e.records) for e in router.replicas]
    stats = router.report(1.0).pipeline
    assert stats["stack_sets_reused"] == sum(r for r, _ in counts)
    assert stats["stack_sets_allocated"] == sum(b - r for r, b in counts)
    for e in router.replicas:
        per = e.pipeline_stats()
        assert per["stack_sets_reused"] + per["stack_sets_allocated"] == \
            len({r.batch for r in e.records})


def test_stack_span_carries_reused(tmp_path):
    eng = make_engine(0)
    eng.submit("m", make_graph(0, SMALL))
    eng.step()                                  # compile and allocate
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i, nv in enumerate((SMALL, LARGE, SMALL), start=1):
            eng.submit("m", make_graph(i, nv))
            eng.step()
    finally:
        jax.profiler.stop_trace()
    reused = {r.batch: r.stack_reused for r in eng.records}
    spans = {s["batch"]: s["reused"] for name, s in trace_events(tmp_path)
             if name == "gnn.stack"}
    assert spans == {b: int(reused[b]) for b in (1, 2, 3)}
    assert [reused[b] for b in (1, 2, 3)] == [True, False, False]
