#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

``<cell>`` names an entry of ``workloads`` in ``BENCHMARK.json``.  The run
builds the cell's deployment from the seed (graph, features, weights),
warms up every shape its traffic uses, then drives the program's entry
points (``try_submit``/``try_submit_nodes`` -> ``result``) for ``--seconds``
under the traffic mix, drains what is in flight, and compares the answers
with a plain float64 reference.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics,
read from a profiler trace of the window), ``device`` and, last,
``checks``: each number compared with its limit.  The comparisons are also
the last lines of standard error.

The run fails, and prints no result, when JAX finds no TPU or fewer chips
than the cell asks for.
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

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import context, counts, loops, spec, trace  # noqa: E402

# JAX's persistent compilation cache, at a fixed path in the checkout.
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / "bench" / ".trace"
# How long after the window closes the run waits for answers in flight.
DRAIN_S = 60.0
WINDOW_ANNOTATION = "bench.window"


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int):
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def schedule_for(dep, traffic: dict, seconds: float, seed: int) -> list:
    r = spec.rng(seed, "traffic")
    if traffic["loop"] == "open":
        times = loops.poisson_schedule(float(traffic["rate_per_s"]), seconds,
                                       r)
        return list(zip(times, dep.items(traffic, len(times), r)))
    if traffic["loop"] == "closed":
        return dep.items(traffic, int(traffic["requests_cycle"]), r)
    raise KeyError(f"unknown loop {traffic['loop']!r}")


def drive(dep, traffic: dict, work, seconds: float, t0: float) -> list:
    clients = int(traffic["clients"])
    if traffic["loop"] == "open":
        return loops.open_loop(dep.server, dep.submit, work, clients,
                               DRAIN_S, seconds, t0)
    return loops.closed_loop(dep.server, dep.submit, work, clients, seconds,
                             DRAIN_S, t0)


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool,
        t_start: float = T_START, trace_dir: Path = TRACE_DIR,
        log=print) -> dict:
    """One run of ``cell``; returns the result object (not yet printed)."""
    import jax

    from repro.common import enable_compilation_cache

    enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    cfg, traffic = cell.config, cell.traffic
    replicas = int(traffic.get("replicas", 1))
    kind = spec.load_module("kinds", cfg["kind"])
    t_build = time.perf_counter()
    dep = kind.build(cfg, seed, replicas)
    t_warm = time.perf_counter()
    dep.warm_up(seed)
    log(f"set-up: {t_build - t_start!r} s to start, "
        f"{t_warm - t_build!r} s to build, "
        f"{time.perf_counter() - t_warm!r} s to warm up", file=sys.stderr)
    work = schedule_for(dep, traffic, seconds, seed)
    for engine in dep.engines:
        engine.reset_metrics()
    traces_before = dep.trace_count()
    dep.server.start()
    if traced:
        trace.start(trace_dir)
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    if traced:
        with jax.profiler.TraceAnnotation(WINDOW_ANNOTATION):
            outcomes = drive(dep, traffic, work, seconds, t0)
        traced_s = time.perf_counter() - t0
        trace.stop()
    else:
        outcomes = drive(dep, traffic, work, seconds, t0)
    dep.server.stop(drain=True)
    compiles = dep.trace_count() - traces_before
    log(f"compiles in the window: {compiles}", file=sys.stderr)

    replica_records = [list(e.records) for e in dep.engines]
    pipe = [e.pipeline_stats() for e in dep.engines]
    ctx = context.Context(
        outcomes=outcomes, slots=dep.slots,
        replica_records=replica_records,
        stack_busy_s=sum(p["stack_busy_s"] for p in pipe),
        exec_busy_s=sum(p["exec_busy_s"] for p in pipe),
        cache_hits=sum(e.cache.stats.hits for e in dep.engines),
        cache_misses=sum(e.cache.stats.misses for e in dep.engines),
        work=dep.work([r for recs in replica_records for r in recs]),
        dims=dep.arch.dims(cfg),
        combines_per_layer=dep.arch.COMBINES_PER_LAYER,
        peak=None)
    device = device_info()
    device["memory_peak_bytes"] = memory_peak_bytes(cell.chips)
    dep.free()
    gc.collect()

    answered = [o for o in outcomes if o.status == loops.OK]
    check = dep.check([(o.item, o.output) for o in answered], seed=seed)
    for i in check["wrong"]:
        answered[i].status = "wrong"
    correct = bool(check["checked"]) and check["max_error"] <= check["limit"]

    if traced:
        ctx.peak = counts.peaks(device["kind"])
        ctx.trace = trace.reduce(trace.find(trace_dir), traced_s, cell.chips,
                                 skip_host=(WINDOW_ANNOTATION,))
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        metrics = layer_metrics(cell, ctx)
    else:
        metrics = end_to_end_metrics(cell, outcomes, seconds, setup_s)

    result = {
        "correct": correct,
        "attempted": len(outcomes),
        "failed": sum(o.status != loops.OK for o in outcomes),
        "metrics": metrics,
        "device": device,
    }
    if traced:
        result["breakdown"] = breakdown(ctx.trace)
    result["checks"] = {
        "max_error": {"value": check["max_error"], "limit": check["limit"]},
        "answers_checked": {"value": check["checked"], "limit": 1},
        "compiles_in_window": {"value": compiles, "limit": 0},
    }
    return result


def end_to_end_metrics(cell, outcomes, seconds: float, setup_s: float) -> dict:
    lat = loops.latencies_s(outcomes, seconds + DRAIN_S)
    values = {
        "setup_s": setup_s,
        "throughput_rps": sum(o.status == loops.OK and o.done_s < seconds
                              for o in outcomes) / seconds,
        "p50_ms": loops.percentile(lat, 50) * 1e3 if lat else None,
        "p95_ms": loops.percentile(lat, 95) * 1e3 if lat else None,
    }
    out = {}
    for m in cell.end_to_end:
        if m["name"] not in values:
            raise KeyError(f"no measurement for end-to-end metric "
                           f"{m['name']!r}")
        if values[m["name"]] is not None:
            out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out


def layer_metrics(cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        value = spec.load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def breakdown(summary) -> dict:
    ops = sorted(summary.ops.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps[:10]]}


def print_checks(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", type=Path, default=TRACE_DIR,
                    help="where the profiler trace is written")
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    # The benchmark gives the program its compilation cache directory.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"finds {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    result = run(cell, args.seed, args.seconds, bool(args.trace),
                 trace_dir=args.trace_dir)
    print_checks(result)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
