"""chip_smoke.py's phases at a tiny size on the CPU (Pallas in interpret mode).

The script itself refuses to run off the TPU; these tests drive its phase
functions directly, so its control flow, references and checks are covered
by every CPU run.
"""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import Graph

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def tiny_graph(seed=0, nv=45, ne=150, f=12):
    rng = np.random.default_rng(seed)
    return Graph(
        edge_src=rng.integers(0, nv, ne).astype(np.int32),
        edge_dst=rng.integers(0, nv, ne).astype(np.int32),
        node_feat=rng.random((nv, f)).astype(np.float32),
    ).validate()


def test_gcn_phase_matches_cpu_reference(smoke):
    res = smoke.serve_gcn(tiny_graph(), 3, hidden=8, requests=3, slots=2,
                          seed=0)
    assert res["shape"] == (45, 3)
    # Both sides are float32 on the CPU here: only rounding order differs.
    assert res["max_error"] < 1e-5
    assert res["first_request_s"] > 0 and res["per_request_s"] > 0
    # Interpret mode leaves no Pallas TPU kernel in the compiled program,
    # which is exactly what the chip check refuses.
    with pytest.raises(AssertionError, match="tpu_custom_call"):
        smoke.assert_kernels_compiled(res["engine"])


def test_node_query_phase_matches_cpu_reference(smoke):
    res = smoke.serve_node_queries(
        host_nodes=300, avg_degree=4, features=8, hidden=8, num_classes=3,
        fanouts=(4, 2), queries=3, slots=2, seed=0)
    assert res["shape"] == (1, 3)
    assert res["max_error"] < 1e-5
    assert res["engine"].report(1.0).cache_hits >= 3  # second pass is warm


def test_max_error_measure(smoke):
    ref = np.array([[1.0, -4.0], [2.0, 0.0]], np.float32)
    assert smoke.max_error(ref, ref) == 0.0
    assert smoke.max_error(ref + 0.1, ref) == pytest.approx(0.1 / 4.0)
    with pytest.raises(AssertionError, match="non-finite"):
        smoke.max_error(np.full_like(ref, np.inf), ref)
    with pytest.raises(AssertionError, match="shape"):
        smoke.max_error(ref[:1], ref)


# The four-chip phase in a child process that sees four host CPU devices
# (the test process itself sees one).
FOUR_DEVICE_SCRIPT = """
import importlib.util, json, sys
import numpy as np
from repro.core import Graph
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
rng = np.random.default_rng(0)
graph = Graph(edge_src=rng.integers(0, 45, 150).astype(np.int32),
              edge_dst=rng.integers(0, 45, 150).astype(np.int32),
              node_feat=rng.random((45, 12)).astype(np.float32)).validate()
res = smoke.four_chip_agreement(graph, 3, hidden=8, requests=4, slots=2,
                                seed=0)
res.pop("wall_s")
print("RESULT::" + json.dumps(res))
"""


def test_four_chip_phase_on_four_host_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(_PATH), "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    proc = subprocess.run([sys.executable, "-c", FOUR_DEVICE_SCRIPT, _PATH],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    (line,) = [l for l in proc.stdout.splitlines()
               if l.startswith("RESULT::")]
    res = json.loads(line[len("RESULT::"):])
    assert res["replica_devices"] == [0, 1, 2, 3]
    assert sum(res["served_per_replica"]) == 4
    for key in ("single_max_error", "mesh_max_error", "router_max_error"):
        assert res[key] < 1e-5, key


def test_main_refuses_the_cpu(smoke, monkeypatch, capsys):
    monkeypatch.setattr("sys.argv", ["chip_smoke.py"])
    assert smoke.main() == 1
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "no TPU" in err
