#!/usr/bin/env python3
"""Bring-up smoke run of the GNN serving path on a TPU.

Drives the main path once, in this one process, through the entry points a
user calls:

1. GCN at Cora's Table-2 shape (2,708 nodes, 10,556 edges, 1,433 features,
   7 classes; hidden 16, as in Kipf & Welling's published GCN), weights from
   a seed, served by a ``GnnServeEngine`` on the fused Pallas backend through
   the always-on loop: ``start``, ``try_submit``, ``result``, ``stop``.
2. GraphSAGE node queries through ``submit_nodes`` against a 200,000-node
   power-law ``HostGraph``, at the settings of
   ``examples/serve_gnn.py --node-queries`` (16 features, fanouts 8x4).

Every answer is checked against a float32 reference: the same params on the
``jnp`` aggregation backend, run on the host CPU in this process.  The run
fails when JAX finds no TPU, and when a served executor's compiled program
holds no Pallas kernel (``tpu_custom_call``): interpret mode or the jnp path
cannot pass for the chip.  Compile times and times per request are
wall-clock set-up figures, not device metrics.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # four chips: one engine vs a
                                         # 4-device mesh vs 4 router replicas

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# Largest |served - reference| allowed, as a fraction of the reference's
# largest magnitude.  The TPU runs float32 matmuls in single-pass bf16 by
# default (8-bit mantissa: each operand rounds by up to 2^-9 relative) and
# accumulates in another order than the CPU; through two layers that left
# errors of about 7e-3 of the output's scale on a v5e.  Exactness to the
# bit is out of reach on the chip, so this bound is 2e-2.
TOLERANCE = 2e-2
RESULT_TIMEOUT_S = 900.0


def max_error(got: np.ndarray, ref: np.ndarray) -> float:
    """max |got - ref| over max |ref| (the tolerance's measure)."""
    if got.shape != ref.shape:
        raise AssertionError(f"shape {got.shape} != reference {ref.shape}")
    if not np.all(np.isfinite(got)):
        raise AssertionError("served output holds non-finite values")
    scale = max(float(np.max(np.abs(ref))), np.finfo(np.float32).tiny)
    return float(np.max(np.abs(got - ref))) / scale


def reference_forward(model, params, graph, edge_weights, node_feats, v, n):
    """float32 jnp-backend forwards on the host CPU: one per feature matrix
    in ``node_feats``, all on ``graph``'s structure."""
    import jax
    import jax.numpy as jnp

    from repro.core import aggregate_backend, partition_graph, to_blocked

    cpu = jax.devices("cpu")[0]
    pg = partition_graph(graph, v=v, n=n, edge_weights=edge_weights)
    with jax.default_device(cpu), aggregate_backend("jnp"):
        bg = to_blocked(pg)
        fwd = jax.jit(lambda p, f: model.apply_blocked(p, bg, f))
        p = jax.device_put(params, cpu)
        return [np.asarray(fwd(p, jnp.asarray(pg.pad_features(f))))
                [: graph.num_nodes] for f in node_feats]


def gcn_requests(graph, count: int) -> list:
    """``count`` requests on ``graph``'s structure (one cached partition),
    each with its own assignment of feature rows."""
    from repro.core import Graph

    return [Graph(edge_src=graph.edge_src, edge_dst=graph.edge_dst,
                  node_feat=np.roll(graph.node_feat, i, axis=0))
            for i in range(count)]


def build_gcn(graph, num_classes: int, hidden: int, seed: int):
    import jax

    from repro.gnn import build_model

    model = build_model("gcn", graph.num_features, num_classes, hidden=hidden)
    return model, model.init(jax.random.PRNGKey(seed))


def gcn_reference(model, params, graphs, v: int, n: int) -> list:
    from repro.serving import gcn_prepare

    prepared, weights = gcn_prepare(graphs[0])
    return reference_forward(model, params, prepared, weights,
                             [g.node_feat for g in graphs], v, n)


def serve_all(server, model_id: str, graphs) -> list:
    """Queue every request, start the always-on loop, collect, stop.

    Queueing before ``start`` spreads a router's requests over its replicas
    by queue length; a single engine serves them the same way either way.
    """
    rids = [server.try_submit(model_id, g) for g in graphs]
    if None in rids:
        raise AssertionError("admission refused a smoke request")
    server.start()
    try:
        return [server.result(rid, timeout=RESULT_TIMEOUT_S) for rid in rids]
    finally:
        server.stop(drain=True)


def serve_gcn(graph, num_classes: int, *, hidden: int, requests: int,
              slots: int, seed: int, backend: str = "pallas_fused") -> dict:
    """Phase 1: whole-graph GCN requests through the always-on loop.

    The first request pays partitioning and compilation; the rest are
    submitted together once it is back.
    """
    from repro.serving import GnnServeEngine, gcn_prepare

    model, params = build_gcn(graph, num_classes, hidden, seed)
    engine = GnnServeEngine(backend=backend, slots=slots, tuner=None)
    engine.register("gcn", model, params, task="node",
                    prepare_fn=gcn_prepare)
    graphs = gcn_requests(graph, requests)
    engine.start()
    try:
        t0 = time.perf_counter()
        first = engine.try_submit("gcn", graphs[0])
        outs = [engine.result(first, timeout=RESULT_TIMEOUT_S)]
        first_s = time.perf_counter() - t0
        t1 = time.perf_counter()
        rids = [engine.try_submit("gcn", g) for g in graphs[1:]]
        outs += [engine.result(rid, timeout=RESULT_TIMEOUT_S) for rid in rids]
        warm_s = (time.perf_counter() - t1) / max(len(rids), 1)
    finally:
        engine.stop(drain=True)
    refs = gcn_reference(model, params, graphs, engine.cfg.v, engine.cfg.n)
    return {
        "engine": engine,
        "first_request_s": first_s,
        "per_request_s": warm_s,
        "max_error": max(max_error(o, r) for o, r in zip(outs, refs)),
        "shape": outs[0].shape,
    }


def serve_node_queries(*, host_nodes: int, avg_degree: int, features: int,
                       hidden: int, num_classes: int, fanouts: tuple,
                       queries: int, slots: int, seed: int,
                       backend: str = "pallas_fused") -> dict:
    """Phase 2: GraphSAGE node queries over a resident ``HostGraph``.

    The same seeds go through twice: the first pass compiles each sampled
    bucket, the second finds the executors and partitions warm.
    """
    import jax

    from repro.gnn import build_model
    from repro.serving import GnnServeEngine, HostGraph, sample_khop

    host = HostGraph.synthetic_power_law(
        host_nodes, avg_degree=avg_degree, num_features=features, seed=seed)
    model = build_model("sage", features, num_classes, hidden=hidden)
    params = model.init(jax.random.PRNGKey(seed))
    engine = GnnServeEngine(backend=backend, slots=slots, tuner=None)
    engine.register("sage", model, params, task="node")
    engine.register_host_graph("hg", host, fanouts=fanouts, rng_seed=seed)
    seeds = np.random.default_rng(seed).choice(host_nodes, queries,
                                               replace=False)
    engine.start()
    try:
        passes = []
        for _ in range(2):
            t0 = time.perf_counter()
            rids = [engine.submit_nodes("sage", [int(s)]) for s in seeds]
            outs = [engine.result(rid, timeout=RESULT_TIMEOUT_S)
                    for rid in rids]
            passes.append(((time.perf_counter() - t0) / queries, outs))
    finally:
        engine.stop(drain=True)
    v, n = engine.cfg.v, engine.cfg.n
    err = 0.0
    for i, s in enumerate(seeds):
        sample = sample_khop(host, [int(s)], fanouts, seed,
                             align=math.lcm(v, n))
        (ref,) = reference_forward(model, params, sample.graph, None,
                                   [sample.graph.node_feat], v, n)
        ref = ref[sample.seed_rows]
        err = max(err, *(max_error(outs[i], ref) for _, outs in passes))
    return {
        "engine": engine,
        "cold_per_query_s": passes[0][0],
        "warm_per_query_s": passes[1][0],
        "max_error": err,
        "shape": passes[0][1][0].shape,
    }


def four_chip_agreement(graph, num_classes: int, *, hidden: int,
                        requests: int, slots: int, seed: int,
                        backend: str = "pallas_fused") -> dict:
    """The same GCN requests on one chip, on a 4-device feature mesh, and on
    four router replicas pinned one per chip.  The one-chip answers are
    checked against the float32 CPU reference, the other two against
    them."""
    import jax

    from repro.launch.mesh import make_data_mesh
    from repro.serving import EngineRouter, GnnServeEngine, gcn_prepare

    devices = jax.devices()
    if len(devices) < 4:
        raise AssertionError(f"--four-chips needs 4 devices, JAX sees "
                             f"{len(devices)}")
    model, params = build_gcn(graph, num_classes, hidden, seed)
    graphs = gcn_requests(graph, requests)
    kwargs = dict(task="node", prepare_fn=gcn_prepare)
    timings = {}

    def run(name, server, **register):
        server.register("gcn", model, params, **kwargs, **register)
        t0 = time.perf_counter()
        outs = serve_all(server, "gcn", graphs)
        timings[name] = time.perf_counter() - t0
        return outs

    engine = GnnServeEngine(backend=backend, slots=slots, tuner=None)
    single = run("single", engine)
    refs = gcn_reference(model, params, graphs, engine.cfg.v, engine.cfg.n)
    meshed = run("mesh", GnnServeEngine(backend=backend, slots=slots,
                                        tuner=None, mesh=make_data_mesh(4)))
    router = EngineRouter(4, backend=backend, slots=slots, tuner=None)
    routed = run("router", router, hot=True)

    replica_devices = []
    for i, replica in enumerate(router.replicas):
        seen = {r.devices for r in replica.records}
        if seen != {(devices[i].id,)}:
            raise AssertionError(f"replica {i} ran on devices {seen}, "
                                 f"expected only device {devices[i].id}")
        replica_devices.append(devices[i].id)
    return {
        "wall_s": timings,
        "single_max_error": max(max_error(s, r)
                                for s, r in zip(single, refs)),
        "mesh_max_error": max(max_error(m, s)
                              for m, s in zip(meshed, single)),
        "router_max_error": max(max_error(r, s)
                                for r, s in zip(routed, single)),
        "replica_devices": replica_devices,
        "served_per_replica": [len(r.records) for r in router.replicas],
    }


def assert_kernels_compiled(engine) -> int:
    """Every executor the engine served with holds a Pallas TPU kernel."""
    keys = engine.pool.keys()
    for model_id, bucket in keys:
        text = engine.pool.lower(engine.registry[model_id],
                                 bucket).compile().as_text()
        if "tpu_custom_call" not in text:
            raise AssertionError(
                f"executor {model_id}/{bucket.describe()} compiled without "
                "a Pallas kernel (tpu_custom_call)")
    return len(keys)


def check_error(name: str, err: float) -> None:
    print(f"{name}: max error {err!r} (tolerance {TOLERANCE})")
    if not err <= TOLERANCE:
        raise AssertionError(f"{name}: error {err!r} exceeds {TOLERANCE}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip agreement phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (device 0 is "
              f"{dev.platform}:{dev.device_kind}); this run does not fall "
              "back to the CPU", file=sys.stderr)
        return 1

    from repro.common import enable_compilation_cache
    from repro.core.aggregate import planner_decisions
    from repro.gnn import load
    from repro.gnn.datasets import TABLE2

    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    print(f"compilation cache: {enable_compilation_cache()}")
    t0 = time.perf_counter()
    cora = load("Cora", seed=args.seed)
    classes = TABLE2["Cora"]["labels"]
    print(f"Cora-shaped graph: {cora.num_nodes} nodes, {cora.num_edges} "
          f"edges, {cora.num_features} features, {classes} classes "
          f"(generated in {time.perf_counter() - t0!r} s)")

    if args.four_chips:
        res = four_chip_agreement(cora, classes, hidden=16, requests=8,
                                  slots=4, seed=args.seed)
        print(f"four chips: wall s {res['wall_s']}")
        print(f"router replicas on devices {res['replica_devices']}, "
              f"served {res['served_per_replica']}")
        check_error("one chip vs float32 CPU reference",
                    res["single_max_error"])
        check_error("4-device mesh vs one chip", res["mesh_max_error"])
        check_error("4 router replicas vs one chip", res["router_max_error"])
    else:
        res = serve_gcn(cora, classes, hidden=16, requests=8, slots=4,
                        seed=args.seed)
        print(f"gcn-cora: answers {res['shape']}; compile: first request "
              f"(partition + compile + run) {res['first_request_s']!r} s "
              f"wall; then {res['per_request_s']!r} s wall per request")
        check_error("gcn-cora vs float32 CPU reference", res["max_error"])
        print(f"gcn-cora: {assert_kernels_compiled(res['engine'])} "
              "executor(s), each compiled with tpu_custom_call")

        res = serve_node_queries(
            host_nodes=200_000, avg_degree=6, features=16, hidden=16,
            num_classes=4, fanouts=(8, 4), queries=16, slots=8,
            seed=args.seed)
        print(f"sage node queries: answers {res['shape']}; "
              f"{res['cold_per_query_s']!r} s wall per query with "
              f"compiles, {res['warm_per_query_s']!r} s warm")
        check_error("sage node queries vs float32 CPU reference",
                    res["max_error"])
        print(f"sage node queries: {assert_kernels_compiled(res['engine'])} "
              "executor(s), each compiled with tpu_custom_call")
        for decision in planner_decisions():
            if decision["backend"] == "pallas_fused":  # not the reference's
                print(f"planner: {decision}")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
