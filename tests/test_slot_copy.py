"""Copy-in by slot: a batch that fills ``k`` of its ``R`` slots ships
only those ``k`` into the pool's device-resident slot set of its shape.

The claims held here:
  * the device inputs the executor receives equal the zero-filled host
    stack byte for byte, through batches of 1, 3, 4 (= R) and 2 requests,
    a switch of bucket and back, on a meshless and on a pinned pool;
  * served answers are bit-identical to a fresh engine's;
  * ``h2d_bytes`` is the filled slots' bytes when ``k < R`` and the whole
    stack's when ``k == R``; ``h2d_slots`` and the ``gnn.h2d`` span's
    ``slots`` agree with it;
  * the slot writer is traced once per stacked shape, when the shape's
    executor is built, never once per slot index or occupancy;
  * the pool keeps at most ``MAX_SLOT_SETS`` sets, dropping the least
    recently used;
  * a run or a slot write that raises leaves the pool serving the next
    batch of that shape correctly;
  * two executor workers under many client threads never see a set
    written under them;
  * a pool sharded over several devices keeps the whole-stack copy.
"""

import dataclasses
import json
import os
import subprocess
import sys
import threading

import jax
import numpy as np
import pytest

from repro.launch.mesh import make_data_mesh
from repro.serving.registry import MAX_SLOT_SETS
from test_stack_buffers import (
    LARGE,
    SLOTS,
    SMALL,
    fresh_stack,
    graphs_of,
    make_engine,
    make_graph,
)
from test_stage_spans import trace_events

# Batches of 1, 3, 4 (= SLOTS) and 2 in one bucket, a switch of bucket,
# and back: each batch fills fewer or more slots than the last of its
# shape, so the set's stale slots must be cleared.
SEQUENCE = [(SMALL, 1), (SMALL, 3), (SMALL, 4), (SMALL, 2), (LARGE, 2),
            (SMALL, 1)]


def spy_inputs(eng):
    """Wrap ``eng.pool.device_args``; returns, per batch, the host stack
    handed in, its occupancy and a host copy of the device inputs (a
    copy: on the CPU a whole-stack input may alias the host stack)."""
    seen = []
    device_args = eng.pool.device_args

    def spy(entry, *batch, filled=None):
        args = device_args(entry, *batch, filled=filled)
        seen.append(([a.copy() for a in batch], filled,
                     [np.array(a) for a in args[1:]]))
        return args

    eng.pool.device_args = spy
    return seen


def bucket_of(eng, rec):
    """The bucket that served ``rec``."""
    return next(b for _, b in eng.pool.keys() if b.describe() == rec.bucket)


def serve(eng, batches, model_id="m"):
    """Step each batch through ``eng``; returns the answers per batch."""
    served = []
    for batch in batches:
        rids = [eng.submit(model_id, g) for g in batch]
        assert eng.step() == len(batch)
        served.append([eng.take_result(r) for r in rids])
    return served


@pytest.mark.parametrize("pinned", [False, True], ids=["meshless", "pinned"])
def test_device_inputs_equal_the_zero_filled_stack(pinned):
    eng = make_engine(0, mesh=make_data_mesh(1) if pinned else None)
    seen = spy_inputs(eng)
    batches = graphs_of(SEQUENCE)
    serve(eng, batches)
    assert len(seen) == len(SEQUENCE)
    for (host, filled, device), (_, k), batch in zip(seen, SEQUENCE,
                                                       batches):
        assert filled == k
        for got, want in zip(device, host):
            assert got.dtype == want.dtype and got.shape == want.shape
            assert got.tobytes() == want.tobytes()
            assert not got[k:].any()
    # Both shapes left a set behind, the last batch's occupancy recorded.
    sets = eng.pool._slot_sets
    assert len(sets) == 2
    assert [s.filled for s in sets.values()] == [2, 1]
    if pinned:
        device = eng.pool.device
        for s in sets.values():
            assert all(a.devices() == {device} for a in s.arrays)


def test_device_inputs_equal_a_fresh_stack_built_from_the_batch():
    """The host stack itself is the zero-filled one (``_StackBuffers``),
    so device inputs equal a stack built fresh from the requests."""
    eng = make_engine(0)
    seen = spy_inputs(eng)
    real_stack = eng._stack
    fresh = []

    def stack(*args):
        sb = real_stack(*args)
        fresh.append(fresh_stack(eng.slots, sb.key[1], sb.batch))
        return sb

    eng._stack = stack
    serve(eng, graphs_of(SEQUENCE))
    for (_, _, device), want in zip(seen, fresh):
        for got, w in zip(device, want):
            assert got.tobytes() == w.tobytes()


@pytest.mark.parametrize("depth", [0, 2])
def test_served_answers_equal_a_fresh_engine(depth):
    batches = graphs_of(SEQUENCE)
    served = serve(make_engine(depth), batches)
    for batch, outs in zip(batches, served):
        fresh = make_engine(depth)
        for want, got in zip(serve(fresh, [batch])[0], outs):
            np.testing.assert_array_equal(got, want)


def test_h2d_bytes_and_slots_count_the_filled_slots(tmp_path):
    eng = make_engine(0)
    serve(eng, graphs_of(SEQUENCE[:1]))          # compile first
    jax.profiler.start_trace(str(tmp_path))
    try:
        serve(eng, graphs_of(SEQUENCE))
    finally:
        jax.profiler.stop_trace()
    records = {r.batch: r for r in eng.records if r.batch >= 1}
    assert len(records) == len(SEQUENCE)
    for (_, k), (_, rec) in zip(SEQUENCE, sorted(records.items())):
        whole = sum(a.nbytes for a in fresh_stack(SLOTS, bucket_of(eng, rec),
                                                  []))
        assert rec.h2d_slots == k
        assert rec.h2d_bytes == whole * k // SLOTS
    spans = {s["batch"]: s["slots"] for name, s in trace_events(tmp_path)
             if name == "gnn.h2d"}
    assert spans == {b: rec.h2d_slots for b, rec in records.items()}


def test_writer_is_traced_once_per_shape_at_executor_build():
    eng = make_engine(0)
    pool = eng.pool
    entry = eng.registry["m"]
    assert pool.writer_trace_count == 0
    eng.submit("m", make_graph(0, SMALL))
    eng.step()
    assert pool.writer_trace_count == 1
    # A new bucket's executor brings its writer before any batch runs.
    (_, small), = pool.keys()
    wide = dataclasses.replace(small, num_blocks=2 * small.num_blocks)
    pool.executor(entry, wide)
    assert pool.writer_trace_count == 2
    assert pool.stack_shape(wide) in pool._writers
    # Every occupancy and slot index, in two shapes, traces nothing more.
    serve(eng, graphs_of(SEQUENCE))
    assert pool.writer_trace_count == len(pool._writers) == 3
    # Nor do the executors retrace on the set's arrays: one trace each of
    # the two that ran (``wide``'s is built, never called).
    assert pool.trace_count == 2


def test_slot_sets_stay_within_their_cap():
    eng = make_engine(0)
    shapes = []
    # Each vertex count is its own bucket; one request leaves slots empty.
    for i, nv in enumerate((8, 12, 20, 40, 70, 130, 12)):
        eng.submit("m", make_graph(i, nv))
        eng.step()
        shapes.append(eng.pool.stack_shape(bucket_of(eng, eng.records[-1])))
        assert len(eng.pool._slot_sets) <= MAX_SLOT_SETS
    assert len(set(shapes)) == 6
    # The least recently used sets went; 12 vertices came back last.
    assert list(eng.pool._slot_sets) == shapes[-MAX_SLOT_SETS:-1] + \
        [shapes[-1]]


@pytest.mark.parametrize("where", ["run", "write"])
def test_a_failure_leaves_the_pool_serving(where):
    eng = make_engine(0)
    # A second catalog entry of the same shapes: its batches form another
    # group (the failed group's writeback stays blocked on the lost
    # batch) but share the pool's slot set.
    entry = eng.registry["m"]
    eng.register("m2", entry.model, entry.params)
    serve(eng, [[make_graph(0, SMALL)]])          # compile, one set
    (shape, _), = eng.pool._slot_sets.items()
    if where == "run":
        real = eng.pool.executor

        def executor(entry, bucket):
            def boom(*args):
                raise RuntimeError("run failed")
            eng.pool.executor = real
            return boom

        eng.pool.executor = executor
    else:
        real = eng.pool._writers[shape]
        calls = []

        def writer(*args):
            calls.append(1)
            if len(calls) == 2:
                raise RuntimeError("write failed")
            return real(*args)

        eng.pool._writers[shape] = writer
    for i in range(3):
        eng.submit("m", make_graph(10 + i, SMALL))
    with pytest.raises(RuntimeError, match=f"{where} failed"):
        eng.step()
    if where == "run":
        # Written in full, so kept, with three slots to clear next.
        assert eng.pool._slot_sets[shape].filled == 3
    else:
        # Dropped half-written; the next batch starts a fresh set.
        assert shape not in eng.pool._slot_sets
        eng.pool._writers[shape] = real
    seen = spy_inputs(eng)
    batch = [make_graph(20, SMALL)]
    got, = serve(eng, [batch], "m2")
    (host, filled, device), = seen
    assert filled == 1 and eng.pool._slot_sets[shape].filled == 1
    for g, w in zip(device, host):
        assert g.tobytes() == w.tobytes()
    want, = serve(make_engine(0), [batch])
    np.testing.assert_array_equal(got[0], want[0])


def test_pipelined_workers_share_slot_sets_safely():
    """Two executor workers and eight client threads, with the thread
    switch interval cut short: every device input still equals its host
    stack, and every answer equals a serial engine's."""
    graphs = [make_graph(100 + i, SMALL if i % 3 else LARGE)
              for i in range(48)]
    eng = make_engine(2)
    seen = spy_inputs(eng)
    rids = [None] * len(graphs)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        eng.start()

        def client(c):
            for i in range(c, len(graphs), 8):
                rids[i] = eng.submit("m", graphs[i])

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
        got = [eng.result(r, timeout=120) for r in rids]
        eng.stop()
    finally:
        sys.setswitchinterval(interval)
    assert any(filled < SLOTS for _, filled, _ in seen)
    for host, _, device in seen:
        for g, w in zip(device, host):
            assert g.tobytes() == w.tobytes()
    serial = make_engine(0)
    for g, out in zip(graphs, got):
        want, = serve(serial, [[g]])
        np.testing.assert_array_equal(out, want[0])


SHARDED_SCRIPT = r"""
import json
import jax
import numpy as np
from repro.core.graph import Graph
from repro.gnn import build_model
from repro.launch.mesh import make_data_mesh
from repro.photonic.perf import GhostConfig
from repro.serving import GnnServeEngine

model = build_model("gcn", 5, 2, hidden=4)
eng = GnnServeEngine(cfg=GhostConfig(v=8, n=8), slots=4, pipeline_depth=0,
                     mesh=make_data_mesh(2))
eng.register("m", model, model.init(jax.random.PRNGKey(0)))
rng = np.random.default_rng(0)
g = Graph(edge_src=rng.integers(0, 12, 36).astype(np.int32),
          edge_dst=rng.integers(0, 12, 36).astype(np.int32),
          node_feat=rng.standard_normal((12, 5)).astype(np.float32)).validate()
eng.submit("m", g)
eng.step()
rec, = eng.records
shape = eng.pool.stack_shape(eng.pool.keys()[0][1])
whole = sum(np.zeros(s, np.float32).nbytes for s in
            [shape[:4], shape[:2], shape[:2], (shape[0], *shape[4:])])
print("RESULT::" + json.dumps(dict(
    copies_slots=eng.pool.copies_slots, slots=rec.h2d_slots,
    bytes=rec.h2d_bytes, whole=whole, sets=len(eng.pool._slot_sets),
    writers=eng.pool.writer_trace_count)))
"""


def test_a_sharded_pool_copies_the_whole_stack():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    proc = subprocess.run([sys.executable, "-c", SHARDED_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    (line,) = [ln for ln in proc.stdout.splitlines()
               if ln.startswith("RESULT::")]
    res = json.loads(line[len("RESULT::"):])
    assert res == dict(copies_slots=False, slots=4, bytes=res["whole"],
                       whole=res["whole"], sets=0, writers=0)
