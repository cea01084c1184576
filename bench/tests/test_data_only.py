"""Adding a cell, a traffic mix and a per-layer metric adds files and
entries, and changes no file of the benchmark."""

import json
import shutil
import subprocess
import sys
import textwrap

from bench import spec

SCRIPT = textwrap.dedent("""
    import json, sys, time
    sys.path[:0] = [{copy!r}, {src!r}]
    from bench import counts, run, spec
    from bench.tests.tiny import CPU_PEAK, tiny_cell
    assert spec.__file__.startswith({copy!r})
    counts.peaks = lambda kind: CPU_PEAK
    res = run.run(tiny_cell("gcn-cora.few-clients"), 5, 1.0, True,
                  t_start=time.perf_counter(), log=lambda *a, **k: None)
    print(json.dumps(res))
""")


def test_new_cell_traffic_and_metric_are_data_only(tmp_path):
    copy = tmp_path / "checkout"
    copy.mkdir()
    shutil.copy(spec.ROOT / "BENCHMARK.json", copy)
    shutil.copytree(spec.BENCH_DIR, copy / "bench",
                    ignore=shutil.ignore_patterns(".trace", "__pycache__"))
    before = {p: p.read_bytes() for p in (copy / "bench").rglob("*")
              if p.is_file()}

    (copy / "bench" / "traffic" / "few-clients.json").write_text(
        json.dumps({"loop": "closed", "clients": 3, "requests_cycle": 64}))
    (copy / "bench" / "metrics" / "answered.wg.py").write_text(
        "def read(ctx):\n    return float(len(ctx.records)) or None\n")
    bench = json.loads((copy / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "gcn-cora.few-clients", "config": "gcn-cora",
        "traffic": "few-clients", "chips": 1, "why": "three clients"})
    bench["per_layer"].append({
        "name": "answered.wg", "unit": "req", "better": "higher",
        "source": "program_counter", "layer": "scheduler and queue",
        "moves": "throughput_rps", "workloads": ["gcn-cora.few-clients"]})
    bench["end_to_end"][0]["workloads"].append("gcn-cora.few-clients")
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))

    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(copy=str(copy),
                                             src=str(spec.ROOT / "src"))],
        capture_output=True, text=True, timeout=600,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": str(tmp_path)})
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"] is True
    assert res["metrics"]["answered.wg"]["value"] > 0
    for path, data in before.items():
        assert path.read_bytes() == data, f"{path} changed"
