"""Multi-model GNN serving driver: one engine, a heterogeneous catalog.

Drives the multi-model continuous-batching engine (repro.serving) the way
GHOST pitches the hardware (Section 4.1): one substrate serving GCN /
GraphSAGE / GIN side by side.  The catalog mixes tasks *and* feature
widths — a trained GIN graph classifier on Mutag (143 features) next to
GCN/GraphSAGE node taggers on Proteins structures (3 features) — so the
request stream exercises model registry, feature-dim bucketing, the
pluggable scheduler, and admission control in one run.

Prints the served-throughput report: functional req/s on this host,
latency percentiles, per-model served counts, admission outcomes, the
preprocessing-cache hit rate, the bounded jit-trace count (<= |models| x
|buckets|), and the analytic GHOST hardware estimate for the same stream.

Run:  PYTHONPATH=src python examples/serve_gnn.py --requests 40 \
          --scheduler occupancy --max-waiting 32

Node-query mode: ``--node-queries`` swaps the per-request graph stream
for GraphSAGE-style neighborhood-sampled serving against one resident
million-scale synthetic power-law host graph (``--host-nodes``).  Each
request names seed vertices; the engine samples a bounded k-hop subgraph
(deterministic per-seed fanouts) and routes it through the same
cache/bucketing/executor machinery.  The skewed (hot-node) seed stream
makes identical resamples share partition-cache entries, which the run
asserts on:

  PYTHONPATH=src python examples/serve_gnn.py --node-queries \
      --host-nodes 200000 --requests 48

Async loop: ``--async-loop`` starts the always-on background serve
thread instead of the caller-driven tick loop — clients just
``try_submit`` from any thread and ``stop(drain=True)`` at the end.  The
catalog is registered with per-model SLOs (``slo_ms``), so the report
gains the deadline-attainment line; pair with ``--scheduler deadline``
to see EDF preemption protect tight-SLO models under load.
``--pipeline-depth N`` sets the serve-loop pipelining: 0 runs the serial
stack-then-execute loop, N >= 1 overlaps host batch stacking with device
execution across N executor workers (bit-exact with serial; the report
then shows exec-busy vs stack-busy overlap fractions):

  PYTHONPATH=src python examples/serve_gnn.py --async-loop \
      --scheduler deadline --requests 60 --pipeline-depth 2

Multi-seed node queries: ``--seeds-per-query K`` batches K seed
vertices into one request in ``--node-queries`` mode; the engine
samples a single shared subgraph and slices one result row per seed —
bit-exact with K solo submissions.

Multi-device: ``--devices N`` builds a 1-D data mesh over the first N
local devices (launch.mesh.make_data_mesh) and hands it to the engine;
every executor trace then partitions its fp32 combine contractions across
the mesh (core.aggregate shard_scope, feature-dim strategy — few-ULP vs
single-device; quantized GIN combines stay single-device since the
per-tensor int8 scale is a global reduction).  On a CPU host, split the
platform into virtual devices first:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/serve_gnn.py --devices 8
"""

import argparse
import time

import jax
import numpy as np

from repro.common import enable_compilation_cache
from repro.gnn import build_model, load
from repro.gnn.train import train_graph_classifier
from repro.photonic.perf import GhostConfig, GnnModelSpec
from repro.serving import GnnServeEngine, HostGraph, gcn_prepare


def run_node_queries(args):
    """Neighborhood-sampled node queries against one resident host graph."""
    f = 16
    host = HostGraph.synthetic_power_law(
        args.host_nodes, avg_degree=6, num_features=f, seed=0)
    print(f"host graph ready: {host.num_nodes} nodes, "
          f"{host.num_edges} edges (synthetic power-law)")

    sage = build_model("sage", f, 4, hidden=16)
    engine = GnnServeEngine(
        cfg=GhostConfig(), slots=args.slots, backend=args.backend,
        scheduler=args.scheduler, max_waiting=args.max_waiting,
        admission_policy=args.admission_policy,
        pipeline_depth=args.pipeline_depth)
    engine.register("sage_host", sage, sage.init(jax.random.PRNGKey(0)),
                    task="node", spec=GnnModelSpec.graphsage(f, 16, 4),
                    slo_ms=100.0 if args.async_loop else None)
    engine.register_host_graph("hg", host, fanouts=(8, 4), rng_seed=0)

    # Skewed seed stream: a small hot set dominates, so deterministic
    # resampling produces identical subgraphs that share cache entries.
    # With --seeds-per-query K each request carries K seeds sampled as one
    # shared subgraph; the result has one row per seed.
    k = args.seeds_per_query
    rng = np.random.default_rng(1)
    hot = rng.permutation(host.num_nodes)[:max(8, args.requests // 6)]
    seeds = hot[rng.integers(0, len(hot), (args.requests, k))]

    t0 = time.perf_counter()
    rids = []
    if args.async_loop:
        engine.start()
        for row in seeds:
            rids.append(engine.try_submit_nodes(
                "sage_host", [int(s) for s in row]))
        engine.stop(drain=True)
    else:
        for i, row in enumerate(seeds):
            rids.append(engine.try_submit_nodes(
                "sage_host", [int(s) for s in row]))
            if (i + 1) % args.slots == 0:
                engine.step()
        engine.drain()
    report = engine.report(time.perf_counter() - t0)

    print(report.pretty())
    served = [rid for rid in rids if rid is not None]
    for rid in served[:1]:
        assert engine.results[rid].shape == (k, 4)
    assert report.node_query_stats["queries"] == len(served)
    assert report.cache_hits > 0, \
        "hot-node stream must share subgraph-level cache entries"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=40)
    ap.add_argument("--slots", type=int, default=8,
                    help="continuous-batching width R")
    ap.add_argument("--working-set", type=int, default=12,
                    help="distinct graphs per dataset the stream cycles over")
    ap.add_argument("--backend", choices=("jnp", "pallas", "pallas_fused"),
                    default="jnp")
    ap.add_argument("--scheduler",
                    choices=("fifo", "occupancy", "deadline"),
                    default="occupancy")
    ap.add_argument("--async-loop", action="store_true",
                    help="serve via the always-on background thread "
                         "(start/try_submit/stop) instead of caller-driven "
                         "ticks; registers per-model SLOs so the report "
                         "shows deadline attainment")
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="serve-loop pipelining under --async-loop: 0 = "
                         "serial stack-then-execute, N >= 1 overlaps host "
                         "stacking with N device-executor workers "
                         "(bit-exact with serial)")
    ap.add_argument("--seeds-per-query", type=int, default=1,
                    help="seed vertices per request in --node-queries mode "
                         "(one shared sampled subgraph, one result row per "
                         "seed)")
    ap.add_argument("--max-waiting", type=int, default=None,
                    help="admission bound on the waiting queue")
    ap.add_argument("--admission-policy", choices=("reject", "shed-oldest"),
                    default="reject")
    ap.add_argument("--quantized", action="store_true",
                    help="route the GIN combines through the photonic 8-bit MVM")
    ap.add_argument("--devices", type=int, default=1,
                    help="partition executor traces over a 1-D mesh of this "
                         "many devices (CPU hosts: set XLA_FLAGS="
                         "--xla_force_host_platform_device_count first)")
    ap.add_argument("--train-steps", type=int, default=60)
    ap.add_argument("--node-queries", action="store_true",
                    help="neighborhood-sampled node queries against one "
                         "resident synthetic power-law host graph")
    ap.add_argument("--host-nodes", type=int, default=200_000,
                    help="host-graph size for --node-queries")
    args = ap.parse_args()
    if args.requests < 1 or args.working_set < 1 or args.slots < 1:
        ap.error("--requests, --working-set and --slots must be >= 1")
    if args.devices < 1:
        ap.error("--devices must be >= 1")
    if args.host_nodes < 100:
        ap.error("--host-nodes must be >= 100")
    if args.seeds_per_query < 1:
        ap.error("--seeds-per-query must be >= 1")
    if args.pipeline_depth < 0:
        ap.error("--pipeline-depth must be >= 0")
    enable_compilation_cache()
    if args.node_queries:
        run_node_queries(args)
        return
    mesh = None
    if args.devices > 1:
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(args.devices)  # raises with the XLA_FLAGS hint

    # Offline: build the catalog.  The GIN graph classifier is trained
    # (deployment-side training); the node taggers ship with fresh params —
    # the serving mechanics are identical either way.
    mutag = load("Mutag", seed=0, num_graphs=max(args.working_set, 60))
    proteins = load("Proteins", seed=0, num_graphs=args.working_set)
    f_gin, f_node = mutag[0].num_features, proteins[0].num_features
    gin = build_model("gin", f_gin, 2, hidden=16, mlp_layers=2)
    gin_params, _ = train_graph_classifier(gin, mutag,
                                           steps=args.train_steps)
    gcn = build_model("gcn", f_node, 2, hidden=16)
    sage = build_model("sage", f_node, 2, hidden=16)
    print(f"catalog ready: gin(f={f_gin}, graph task, trained), "
          f"gcn/sage(f={f_node}, node task); starting serving loop")

    cfg = GhostConfig()
    engine = GnnServeEngine(
        cfg=cfg, slots=args.slots, backend=args.backend,
        scheduler=args.scheduler, max_waiting=args.max_waiting,
        admission_policy=args.admission_policy, mesh=mesh,
        pipeline_depth=args.pipeline_depth)
    # Under --async-loop the catalog carries SLO contracts: the graph
    # classifier is latency-tolerant, the node taggers are interactive.
    slo = {"gin": 250.0, "gcn": 50.0, "sage": 100.0} if args.async_loop \
        else {"gin": None, "gcn": None, "sage": None}
    engine.register("gin_mutag", gin, gin_params, task="graph",
                    spec=GnnModelSpec.gin(f_gin, 16, 2, mlp_layers=2),
                    quantized=args.quantized, dataset_name="Mutag",
                    slo_ms=slo["gin"])
    engine.register("gcn_proteins", gcn,
                    gcn.init(jax.random.PRNGKey(1)), task="node",
                    spec=GnnModelSpec.gcn(f_node, 16, 2),
                    prepare_fn=gcn_prepare, dataset_name="Proteins",
                    slo_ms=slo["gcn"])
    engine.register("sage_proteins", sage,
                    sage.init(jax.random.PRNGKey(2)), task="node",
                    spec=GnnModelSpec.graphsage(f_node, 16, 2),
                    dataset_name="Proteins", slo_ms=slo["sage"])

    # Request stream: cycle hot working sets (repeat structures -> the
    # preprocessing cache earns its keep), mixing the catalog 2:1:1.
    rng = np.random.default_rng(0)
    hot_mutag = mutag[: args.working_set]
    stream = []
    for _ in range(args.requests):
        r = rng.random()
        if r < 0.5:
            stream.append(("gin_mutag",
                           hot_mutag[int(rng.integers(0, len(hot_mutag)))]))
        else:
            mid = "gcn_proteins" if r < 0.75 else "sage_proteins"
            stream.append((mid,
                           proteins[int(rng.integers(0, len(proteins)))]))
    if args.async_loop:
        # Always-on loop: the background thread forms batches while
        # clients submit; stop(drain=True) serves the tail before joining.
        engine.start()
        t0 = time.perf_counter()
        rids = [engine.try_submit(mid, g) for mid, g in stream]
        engine.stop(drain=True)
        report = engine.report(time.perf_counter() - t0)
    elif args.max_waiting is None:
        report = engine.run(stream)
        rids = list(range(len(stream)))
    else:
        # Open loop: paced arrivals against the bounded queue, so the
        # admission knobs actually bite (closed-loop run() drains ahead of
        # the bound and never rejects or sheds).
        t0 = time.perf_counter()
        rids = []
        for i, (mid, g) in enumerate(stream):
            rids.append(engine.try_submit(mid, g))
            if (i + 1) % args.slots == 0:
                engine.step()
        engine.drain()
        report = engine.report(time.perf_counter() - t0)

    gin_rids = [(rid, g) for rid, (mid, g) in zip(rids, stream)
                if mid == "gin_mutag" and rid is not None
                and rid in engine.results]
    correct = sum(int(np.argmax(engine.results[rid]) == g.graph_label)
                  for rid, g in gin_rids)
    print(report.pretty())
    if gin_rids:
        print(f"  gin accuracy over stream: {correct / len(gin_rids):.3f}")
    assert report.cache_hit_rate > 0, "working-set stream must hit the cache"
    assert report.traces_compiled <= 3 * len(report.buckets), \
        "executor pool must bound the jit trace count"
    assert set(report.per_model) <= {"gin_mutag", "gcn_proteins",
                                     "sage_proteins"}


if __name__ == "__main__":
    main()
