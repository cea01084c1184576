#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 1 2 3 ... [--control-seeds 1 2 3] [--out FILE]

For each of ``--seeds`` it makes a run of the cell, window and all, at the
cell's own load and sizes, and reads the widest gap of the answers from
the reference: the program's readings, whose largest is the lower end of
the limit.  For each of ``--control-seeds`` it reads the same gap for the
reference itself computed one step of precision lower (the control: see
``bench/precision.py``), on as many answers as a run checks: the smallest
of those is the upper end.  The benchmark's own runs never run this.
Each reading is a JSON line on standard output (and in ``--out``).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import run as bench_run  # noqa: E402
from bench import spec  # noqa: E402

CONTROLS = ("float8_e4m3", "bfloat16")


def control_readings(cell: spec.Cell, seed: int, precisions=CONTROLS) -> dict:
    """The reference at each lower precision against the float64 one, on
    the inputs of ``seed``'s run: as many answers as a run checks."""
    cfg = cell.config
    kind = spec.load_module("kinds", cfg["kind"])
    dep = kind.build(cfg, seed, 1)
    dep.free()
    count = int(cfg["check"].get("sample", cfg.get("request_pool", 1)))
    return {p: dep.control(p, count, seed) for p in precisions}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(bench_run.CACHE_DIR)
    import jax

    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < cell.chips:
        print("calibrate: the cell's chips are not here", file=sys.stderr)
        return 2
    out = open(args.out, "a") if args.out else None
    try:
        def emit(row: dict) -> None:
            line = json.dumps(row)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()

        for seed in args.seeds:
            t0 = time.perf_counter()
            res = bench_run.run(cell, seed, args.seconds, False,
                                t_start=t0)
            emit({"cell": cell.name, "seed": seed, "side": "program",
                  "correct": res["correct"], "failed": res["failed"],
                  "attempted": res["attempted"], "checks": res["checks"],
                  "metrics": res["metrics"]})
            gc.collect()
        for seed in args.control_seeds:
            for prec, gap in control_readings(cell, seed).items():
                emit({"cell": cell.name, "seed": seed, "side": prec,
                      "max_error": gap})
            gc.collect()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
