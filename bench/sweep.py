#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest offered rate it sustains.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 100 200 300 ...

One deployment is built and warmed up; then each rate runs the cell's
traffic mix at that rate for ``--seconds``.  A rate is sustained when the
backlog does not grow over the window: the answers returned within the
window are at least 90% of the requests sent, and the latency of the last
fifth of the requests stays within twice that of the first fifth.  How
late the load generator ran is reported beside it.  Each rate prints one
JSON line.  The benchmark's own runs never run this: the
rate a cell offers is fixed in its traffic file.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import loops, spec  # noqa: E402
from bench import run as bench_run  # noqa: E402

GROWTH_LIMIT = 2.0
ANSWERED_SHARE = 0.90


def step(dep, traffic: dict, rate: float, seconds: float, seed: int) -> dict:
    traffic = dict(traffic, rate_per_s=rate)
    work = bench_run.schedule_for(dep, traffic, seconds, seed)
    for e in dep.engines:
        e.reset_metrics()
    t0 = time.perf_counter()
    outcomes = bench_run.drive(dep, traffic, work, seconds, t0)
    lat = loops.latencies_s(outcomes, seconds + bench_run.DRAIN_S)
    fifth = max(1, len(lat) // 5)
    first = loops.percentile(lat[:fifth], 50)
    last = loops.percentile(lat[-fifth:], 50)
    lag = loops.percentile(loops.lags_s(outcomes), 95) * 1e3
    done = [o for o in outcomes if o.status == loops.OK]
    in_window = sum(o.done_s < seconds for o in done)
    return {
        "rate": rate, "offered": len(outcomes), "answered": len(done),
        "answered_per_s": in_window / seconds,
        "p50_ms": loops.percentile(lat, 50) * 1e3,
        "p95_ms": loops.percentile(lat, 95) * 1e3,
        "gen_lag_p95_ms": lag, "growth": last / first,
        "sustained": last / first <= GROWTH_LIMIT
        and in_window >= ANSWERED_SHARE * len(outcomes),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        print("sweep: only open-loop cells have a knee", file=sys.stderr)
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(bench_run.CACHE_DIR)
    import jax

    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < cell.chips:
        print("sweep: the cell's chips are not here", file=sys.stderr)
        return 2
    from repro.common import enable_compilation_cache

    enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    kind = spec.load_module("kinds", cell.config["kind"])
    dep = kind.build(cell.config, args.seed,
                     int(cell.traffic.get("replicas", 1)))
    dep.warm_up(args.seed)
    dep.server.start()
    try:
        for i, rate in enumerate(args.rates):
            row = step(dep, cell.traffic, rate, args.seconds, args.seed + i)
            print(json.dumps({"cell": cell.name, **row}), flush=True)
    finally:
        dep.server.stop(drain=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
