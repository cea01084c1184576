"""The check that decides ``correct`` fails a broken timed path.

Each test skips the harness's look for a chip, drives the rest of a run
at a tiny size, and breaks the program underneath: the executor's output
is altered where it is produced.
"""

import jax.numpy as jnp
import pytest

from bench.tests.tiny import run_tiny


def _break_executor(monkeypatch, fault):
    from repro.serving.registry import ExecutorPool

    real = ExecutorPool.executor

    def broken(self, entry, bucket):
        exe = real(self, entry, bucket)
        return lambda *args: fault(exe(*args))

    monkeypatch.setattr(ExecutorPool, "executor", broken)


def _half_batch(out):
    """Half of the batch's slots left out (their answers zero)."""
    return out.at[: (out.shape[0] + 1) // 2].set(0.0)


def _altered(out):
    """Every answer's first logit altered."""
    return out.at[..., 0].add(1.0)


@pytest.mark.parametrize("fault", [_half_batch, _altered],
                         ids=["half_batch", "altered_answer"])
@pytest.mark.parametrize("name", ["gcn-cora.closed", "sage-reddit.zipf"])
def test_broken_path_is_not_correct(name, fault, monkeypatch):
    _break_executor(monkeypatch, fault)
    res = run_tiny(name)
    assert res["correct"] is False
    assert res["checks"]["max_error"]["value"] > res["checks"]["max_error"][
        "limit"]
    assert res["failed"] > 0


def test_control_is_not_correct():
    """The reference computed in fp8 (the control) reads over the limit,
    at a tiny size; bfloat16 reads under it (see PERF.md)."""
    from bench import spec
    from bench.tests.tiny import tiny_cell

    for name in ("gcn-cora.closed", "sage-reddit.zipf"):
        cfg = tiny_cell(name).config
        dep = spec.load_module("kinds", cfg["kind"]).build(cfg, 11, 1)
        dep.free()
        limit = cfg["check"]["max_error"]
        assert dep.control("float8_e4m3", 8, 11) > limit
        assert dep.control("float64", 8, 11) == 0.0
