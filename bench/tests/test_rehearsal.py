"""A CPU rehearsal of bench/run.py on every cell, at a tiny size."""

import json
import shutil
import subprocess
import sys

import pytest

from bench import run as bench_run
from bench import spec
from bench.tests.tiny import run_tiny

CELLS = [w["name"] for w in spec.load_json(spec.ROOT / "BENCHMARK.json")
         ["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_is_correct(name):
    res = run_tiny(name)
    cell = spec.load_cell(name)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["checks"]["compiles_in_window"]["value"] == 0
    json.dumps(res)


def test_traced_run_reads_host_layers(tmp_path, monkeypatch):
    res = run_tiny("gcn-cora.closed", traced=True, trace_dir=tmp_path / "t",
                   monkeypatch=monkeypatch)
    assert res["correct"] is True
    for name in ("batch_fill.wg", "stack_ms.wg", "exec_ms.wg"):
        assert res["metrics"][name]["value"] > 0
    # No device plane on the CPU: device metrics stay silent, never 0.
    assert "mfu.wg" not in res["metrics"]
    assert "agg_roofline.wg" not in res["metrics"]
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_tpu_means_no_result(capsys):
    assert bench_run.main(["--workload", "gcn-cora.closed", "--seed", "1",
                           "--seconds", "1"]) == 2
    out, err = capsys.readouterr()
    assert out == "" and "TPU" in err


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".trace", "__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gcn-cora.closed",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": str(tmp_path)})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_every_metric_has_a_reader_and_every_cell_its_files():
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    for m in bench["per_layer"]:
        assert (spec.BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        traffic = spec.BENCH_DIR / "traffic" / f"{w['traffic']}.json"
        assert traffic.is_file()
