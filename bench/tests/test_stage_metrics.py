"""The readers of the engine's stage spans and counters, on synthetic
records, and a CPU rehearsal of the four-replica cell on four host
devices."""

import json
import os
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import pytest

from bench import context, spec

NEW = ("prep_ms.wg", "prep_ms.nq", "h2d_ms.wg", "h2d_ms.nq", "h2d_mb.wg",
       "h2d_mb.nq", "device_wait_ms.wg", "device_wait_ms.nq", "pickup_ms.nq")


def rec(node_query, batch, **fields):
    return SimpleNamespace(node_query=node_query, batch=batch, **fields)


def ctx_of(replica_records):
    return context.Context(
        outcomes=[], slots=2, replica_records=replica_records,
        stack_busy_s=0.0, exec_busy_s=0.0, cache_hits=0, cache_misses=0,
        work=[], dims=[], combines_per_layer=1, peak=None)


def read(name, ctx):
    return spec.load_module("metrics", name).read(ctx)


def staged(nq, batch, h2d_s, nbytes, wait_s, prep_s, pickup_s):
    return rec(nq, batch, prep_s=prep_s, h2d_s=h2d_s, h2d_bytes=nbytes,
               device_wait_s=wait_s, pickup_s=pickup_s)


@pytest.mark.parametrize("nq", [False, True])
def test_per_batch_readers_count_each_batch_once(nq):
    k = "nq" if nq else "wg"
    # Replica 0: batch 0 of two requests, batch 1 of one; replica 1 has its
    # own batch 0, which is another batch.
    replicas = [
        [staged(nq, 0, 0.010, 4_000_000, 0.001, 0.002, 0.001),
         staged(nq, 0, 0.010, 4_000_000, 0.001, 0.004, 0.002),
         staged(nq, 1, 0.030, 2_000_000, 0.003, 0.006, 0.003)],
        [staged(nq, 0, 0.020, 3_000_000, 0.002, 0.008, 0.050)],
    ]
    ctx = ctx_of(replicas)
    assert read(f"h2d_ms.{k}", ctx) == pytest.approx(20.0)
    assert read(f"h2d_mb.{k}", ctx) == pytest.approx(3.0)
    assert read(f"device_wait_ms.{k}", ctx) == pytest.approx(2.0)
    assert read(f"prep_ms.{k}", ctx) == pytest.approx(4.0)
    if nq:
        assert read("pickup_ms.nq", ctx) == pytest.approx(50.0)
    # The other kind's readers find nothing of theirs.
    other = "wg" if nq else "nq"
    for name in NEW:
        if name.endswith(other):
            assert read(name, ctx) is None


def test_pickup_leaves_out_answers_never_taken():
    ctx = ctx_of([[staged(True, 0, 0.01, 1, 0.0, 0.0, None),
                   staged(True, 0, 0.01, 1, 0.0, 0.0, 0.004)]])
    assert read("pickup_ms.nq", ctx) == pytest.approx(4.0)


@pytest.mark.parametrize("name", NEW)
def test_readers_are_silent_without_the_fields(name):
    # Records of a program without stage fields (the parent commit's).
    ctx = ctx_of([[SimpleNamespace(node_query=True),
                   SimpleNamespace(node_query=False)]])
    assert read(name, ctx) is None
    assert read(name, ctx_of([[]])) is None


REHEARSAL = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{root!r}, {src!r}]
    from pathlib import Path
    import jax
    from bench import counts
    from bench.tests.tiny import CPU_PEAK, run_tiny
    assert len(jax.devices()) == 4
    counts.peaks = lambda kind: CPU_PEAK
    res = run_tiny("sage-reddit.zipf-r4", traced=True,
                   trace_dir=Path({trace!r}))
    print(json.dumps(res))
""")


def test_router_cell_rehearses_on_four_host_devices(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", REHEARSAL.format(
            root=str(spec.ROOT), src=str(spec.ROOT / "src"),
            trace=str(tmp_path / "trace"))],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["checks"]["compiles_in_window"]["value"] == 0
    cell = spec.load_cell("sage-reddit.zipf-r4")
    assert cell.chips == 4 and cell.traffic["replicas"] == 4
    assert set(res["metrics"]) == {m["name"] for m in cell.per_layer} == {
        "prep_ms.nq", "h2d_ms.nq", "h2d_mb.nq", "device_wait_ms.nq",
        "pickup_ms.nq"}
