"""The readers of the engine's stack-buffer reuse counter, on synthetic
records."""

from types import SimpleNamespace

import pytest

from bench import context, spec

REUSE = ("stack_reuse.wg", "stack_reuse.nq")


def rec(node_query, batch, **fields):
    return SimpleNamespace(node_query=node_query, batch=batch, **fields)


def ctx_of(replica_records):
    return context.Context(
        outcomes=[], slots=2, replica_records=replica_records,
        stack_busy_s=0.0, exec_busy_s=0.0, cache_hits=0, cache_misses=0,
        work=[], dims=[], combines_per_layer=1, peak=None)


def read(name, ctx):
    return spec.load_module("metrics", name).read(ctx)


@pytest.mark.parametrize("nq", [False, True])
def test_stack_reuse_counts_each_batch_once(nq):
    k = "nq" if nq else "wg"
    # Replica 0: batch 0 of three requests (fresh), batch 1 of one
    # (reused); replica 1's batch 0 is another batch (reused), and its
    # batch 1 of two requests too: 3 of 4 batches reused.
    replicas = [
        [rec(nq, 0, stack_reused=False), rec(nq, 0, stack_reused=False),
         rec(nq, 0, stack_reused=False), rec(nq, 1, stack_reused=True)],
        [rec(nq, 0, stack_reused=True), rec(nq, 1, stack_reused=True),
         rec(nq, 1, stack_reused=True)],
    ]
    ctx = ctx_of(replicas)
    assert read(f"stack_reuse.{k}", ctx) == pytest.approx(75.0)
    other = "wg" if nq else "nq"
    assert read(f"stack_reuse.{other}", ctx) is None


@pytest.mark.parametrize("name", REUSE)
def test_stack_reuse_is_silent_without_the_field(name):
    # Records of a program that stacks into fresh arrays.
    ctx = ctx_of([[rec(True, 0), rec(False, 0)]])
    assert read(name, ctx) is None
    assert read(name, ctx_of([[]])) is None


@pytest.mark.parametrize("name", REUSE)
def test_stack_reuse_is_registered_for_the_cells_that_stack(name):
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["layer"] == "host stacking" and entry["unit"] == "%"
    for cell in entry["workloads"]:
        assert name in {m["name"] for m in spec.load_cell(cell).per_layer}
