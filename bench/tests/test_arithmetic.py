"""Work counts, percentiles, latency and lateness."""

import math

import numpy as np
import pytest

from bench import counts, loops, spec
from bench.precision import Precision, max_error

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_model_flops_counts_combine_and_narrow_aggregation():
    # GCN 1433 -> 16 -> 7 on Cora with its loops: 2,708 vertices, 13,264
    # edges.  Layer 1 aggregates at 16 wide, layer 2 at 7.
    dims = [(1433, 16), (16, 7)]
    got = counts.model_flops(dims, 1, 2708, 13264)
    want = (2 * 13264 * 16 + 2 * 2708 * 1433 * 16
            + 2 * 13264 * 7 + 2 * 2708 * 16 * 7)
    assert got == want
    # GraphSAGE applies two weight matrices per layer.
    assert counts.model_flops([(4, 3)], 2, 5, 7) == (2 * 7 * 3
                                                     + 2 * 2 * 5 * 4 * 3)


def test_aggregation_work_and_bound():
    flops, nbytes = counts.aggregation_work([(8, 4)], vertices=10, edges=30)
    assert flops == 2 * 30 * 4
    assert nbytes == 4 * 30 + 2 * 4 * 10 * 4
    t, bound = counts.least_time_s(flops, nbytes, PEAK)
    assert bound == "memory"
    assert t == pytest.approx(nbytes / PEAK["hbm_bytes_per_s"])
    t, bound = counts.least_time_s(1e15, 1.0, PEAK)
    assert bound == "compute" and t == pytest.approx(1e15 / 197e12)


def test_peaks_known_and_unknown_device():
    assert counts.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        counts.peaks("cpu")


def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert loops.percentile(values, 50) == 50
    assert loops.percentile(values, 95) == 95
    assert loops.percentile([3.0], 95) == 3.0
    assert loops.percentile([5, 1, 4, 2, 3], 50) == 3
    with pytest.raises(ValueError):
        loops.percentile([], 50)


def test_latency_runs_from_due_and_failures_wait_until_give_up():
    ok = loops.Outcome(item=0, due_s=1.0, sent_s=1.2, done_s=1.5,
                       status=loops.OK)
    late = loops.Outcome(item=0, due_s=2.0, sent_s=2.5, status=loops.REFUSED)
    never = loops.Outcome(item=0, due_s=3.0)  # never sent
    assert loops.latencies_s([ok, late, never], 10.0) == [0.5, 8.0, 7.0]
    assert loops.lags_s([ok, late, never]) == pytest.approx([0.2, 0.5])


def test_poisson_schedule_is_seeded_and_inside_the_window():
    a = loops.poisson_schedule(100.0, 5.0, spec.rng(7, "traffic"))
    b = loops.poisson_schedule(100.0, 5.0, spec.rng(7, "traffic"))
    assert a == b and all(0 < t < 5.0 for t in a)
    assert a == sorted(a) and 400 < len(a) < 600


def test_seed_streams_take_any_whole_seed():
    big = 2 ** 31 + 12345
    assert spec.sub_seed(big, "x") == spec.sub_seed(big, "x")
    assert spec.sub_seed(big, "x") != spec.sub_seed(big, "y")
    assert 0 <= spec.sub_seed(-3, "x") < 2 ** 31


def test_precisions_and_gap():
    x = np.linspace(-3, 3, 101)
    assert np.array_equal(Precision("float64").op(x), x)
    bf = Precision("bfloat16").op(x)
    assert 0 < np.max(np.abs(bf - x)) <= 3 * 2.0 ** -8
    f8 = Precision("float8_e4m3").op(x)
    assert np.max(np.abs(f8 - x)) > np.max(np.abs(bf - x))
    ref = np.array([[1.0, -2.0]])
    assert max_error(ref + [[0.0, 0.02]], ref) == pytest.approx(0.01)
    assert max_error(np.array([[1.0, math.nan]]), ref) == math.inf
    assert max_error(np.zeros((2, 2)), ref) == math.inf
