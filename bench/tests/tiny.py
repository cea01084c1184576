"""Every cell at a size the CPU runs in seconds: the same code paths,
with the graph, the widths and the load cut down."""

import time

from bench import counts, spec

CPU_PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def tiny_cell(name: str) -> spec.Cell:
    cell = spec.load_cell(name)
    cfg = cell.config
    if cfg["kind"] == "whole_graph":
        cfg.update(num_features=40, hidden=16)
        cfg["graph"].update(num_nodes=120, num_edges=400)
        cfg["engine"]["slots"] = 2
        cfg["request_pool"] = 3
    else:
        cfg.update(num_features=12, hidden=16, fanouts=[4, 3])
        cfg["graph"].update(num_nodes=3000, avg_degree=8)
        cfg["engine"]["slots"] = 2
        cfg["check"]["sample"] = 12
    if cell.traffic["loop"] == "open":
        cell.traffic["rate_per_s"] = 20
    return cell


def run_tiny(name: str, seed: int = 2 ** 31 + 7, traced: bool = False,
             seconds: float = 1.0, trace_dir=None, monkeypatch=None):
    from bench import run as bench_run

    if monkeypatch is not None:
        monkeypatch.setattr(counts, "peaks", lambda kind: CPU_PEAK)
    kwargs = {"trace_dir": trace_dir} if trace_dir else {}
    return bench_run.run(tiny_cell(name), seed, seconds, traced,
                         t_start=time.perf_counter(),
                         log=lambda *a, **k: None, **kwargs)
