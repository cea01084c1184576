"""What a cell is: its entries in ``BENCHMARK.json`` and the data files
they name, found by name.

- ``bench/configs/<config>.json``: a configuration (model, graph, engine);
- ``bench/traffic/<traffic>.json``: a traffic mix;
- ``bench/kinds/<kind>.py``: the deployment a configuration's ``kind`` names;
- ``bench/models/<arch>.py``: weights and plain reference of an ``arch``;
- ``bench/metrics/<metric>.py``: the reader of one per-layer metric.

Adding a cell, a configuration, a traffic mix or a metric adds files and
entries; no file here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list      # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in reported


def load_cell(name: str) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics."""
    bench = load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    config = load_json(ROOT / cfg_entry["file"])
    traffic = load_json(BENCH_DIR / "traffic" / f"{entry['traffic']}.json")
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _applies(m, name, reported)]
    return Cell(name=name, chips=int(entry["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH_DIR / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def rng(seed: int, *stream) -> np.random.Generator:
    """Generator for one named stream of a run's seed.

    Streams are told apart by their names, so adding one never shifts the
    draws of another; any whole seed works, negative or over 64 bits.
    """
    words = [int(seed) % (1 << 128)]
    words += [int.from_bytes(s.encode(), "little") if isinstance(s, str)
              else int(s) for s in stream]
    return np.random.default_rng(words)


def sub_seed(seed: int, *stream) -> int:
    """A 31-bit integer seed for one stream (for JAX keys and the engine)."""
    return int(rng(seed, *stream).integers(0, 2 ** 31 - 1))
