"""The reduction from a profiler trace to device time, on a trace recorded
on one TPU v5e: one second of gcn-cora.closed (6 executor runs)."""

import gzip
import shutil
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data" / "gcn-cora-1s.xplane.pb.gz"


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(DATA) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace.reduce(path, window_s=1.0, devices_used=1,
                        skip_host=("bench.window",))


def test_programs_and_busy_time(summary):
    assert summary.devices == 1
    assert list(summary.module_runs.values()) == [6]
    assert all(name.startswith("jit_fwd") for name in summary.modules)
    program_s = sum(summary.modules.values())
    # Ops run inside the programs: busy time is their union, which the
    # programs' own spans cover.
    assert 0 < summary.busy_s <= program_s * 1.001
    assert summary.busy_s == pytest.approx(0.060498402, rel=1e-6)


def test_kernels_are_named_and_counted(summary):
    assert summary.kernel_s == pytest.approx(0.039191998, rel=1e-6)
    top = max(summary.ops, key=summary.ops.get)
    assert top == "pallas f32[4,6144,128]"
    assert summary.ops[top] == pytest.approx(summary.kernel_s)
    assert not any(k.startswith(("while", "call ")) for k in summary.ops)


def test_idle_gaps_are_named_by_host_activity(summary):
    assert len(summary.idle_gaps) == 10
    longest = summary.idle_gaps[0]
    assert longest[1] > 0.2 and longest[0] != "bench.window"
    assert [g[1] for g in summary.idle_gaps] == sorted(
        (g[1] for g in summary.idle_gaps), reverse=True)


def test_union_and_op_names():
    covered, merged = trace._union_s([(0, 10), (5, 20), (30, 40)])
    assert covered == pytest.approx(30e-9) and merged == [[0, 20], [30, 40]]
    assert trace.op_kind("%copy.18 = f32[4,8192,20,20]{3,2,1,0:T(8,128)} "
                         "copy(f32[4,8192,20,20]{1,0,3,2} %blocks.1)") == \
        "copy f32[4,8192,20,20]"
    name = ('%closed_call.20 = f32[6144,256]{1,0} custom-call(s32[256]{0} '
            '%b), custom_call_target="tpu_custom_call"')
    assert trace.is_kernel(name) and trace.op_kind(name) == \
        "pallas f32[6144,256]"
    assert not trace.is_kernel("%fusion = pred[1024]{0} fusion(s32[3]{0} "
                               "%a), kind=kCustom")
