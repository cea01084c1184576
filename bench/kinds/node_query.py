"""Node queries against one large resident graph.

The configuration fixes the graph's structure (a seeded generator); the
run's seed draws its features, the weights and the sampling policy's seed.
Each request asks for the logits of one vertex: the program samples its
k-hop neighbourhood in the caller's thread, partitions it (or finds it in
its cache), batches it, and answers the vertex's row.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench import graphs
from bench.kinds import common
from bench.precision import Precision, max_error
from bench.sampling import InEdges, sample
from bench.spec import load_module, rng, sub_seed
from repro.serving.bucketing import Bucket

HOST = "graph"
# Warm-up queries per replica: enough to meet every sampled-subgraph
# bucket that more than a few in a thousand queries land in.
WARM_QUERIES = 64


class Deployment(common.Deployment):
    def __init__(self, cfg: dict, seed: int, replicas: int):
        from repro.serving import HostGraph

        self.cfg = cfg
        self.arch = load_module("models", cfg["arch"])
        g = cfg["graph"]
        if g["generator"] != "power_law":
            raise KeyError(f"unknown graph generator {g['generator']!r}")
        self.num_nodes = int(g["num_nodes"])
        self.src, self.dst = graphs.power_law_edges(
            self.num_nodes, int(g["avg_degree"]), int(g["structure_seed"]),
            float(g["exponent"]))
        feat = graphs.float_features(self.num_nodes, cfg["num_features"],
                                     sub_seed(seed, "features"))
        self.host = HostGraph.from_edges(self.src, self.dst, feat, name=HOST)
        self.fanouts = tuple(cfg["fanouts"])
        self.rng_seed = sub_seed(seed, "sampling")
        super().__init__(cfg, seed, replicas)
        self.register(task="node")
        self.server.register_host_graph(HOST, self.host, fanouts=self.fanouts,
                                        rng_seed=self.rng_seed)

    def items(self, traffic: dict, count: int, r: np.random.Generator):
        """``count`` query vertices drawn as the traffic's ``seeds`` say."""
        dist = traffic["seeds"]
        if dist["dist"] == "uniform":
            return r.integers(0, self.num_nodes, count).tolist()
        if dist["dist"] == "zipf":
            p = np.arange(1, self.num_nodes + 1, dtype=np.float64) ** (
                -float(dist["exponent"]))
            p /= p.sum()
            perm = r.permutation(self.num_nodes)
            return perm[r.choice(self.num_nodes, size=count, p=p)].tolist()
        raise KeyError(f"unknown seed distribution {dist['dist']!r}")

    def submit(self, item):
        return self.server.try_submit_nodes(common.MODEL_ID, [int(item)])

    def warm_up(self, seed: int) -> None:
        """Serve a uniform warm-up stream on every replica, then compile,
        around each bucket it met, the buckets with half and twice the
        tiles and the groups: the rare subgraphs with fewer or more edges
        or vertices than the common ones land there."""
        import jax

        r = rng(seed, "warm queries")
        for engine in self.engines:
            engine.start()
            try:
                rids = [engine.try_submit_nodes(common.MODEL_ID, [int(v)])
                        for v in r.integers(0, self.num_nodes, WARM_QUERIES)]
                for rid in rids:
                    engine.result(rid, timeout=common.WARM_TIMEOUT_S)
            finally:
                engine.stop(drain=True)
            entry = engine.registry[common.MODEL_ID]
            seen = [bucket for _, bucket in engine.pool.keys()]
            near = {dataclasses.replace(
                b, num_blocks=int(b.num_blocks * tiles),
                num_dst_groups=int(b.num_dst_groups * groups),
                num_src_groups=int(b.num_src_groups * groups))
                for b in seen
                for tiles in (0.5, 1, 2) for groups in (0.5, 1, 2)
                if b.num_blocks * tiles >= 1
                and b.num_dst_groups * groups >= 1}
            for wide in sorted(near - set(seen), key=Bucket.describe):
                exe = engine.pool.executor(entry, wide)
                s = engine.slots
                args = engine.pool.device_args(
                    entry,
                    np.zeros((s, wide.num_blocks, wide.v, wide.n), np.float32),
                    np.zeros((s, wide.num_blocks), np.int32),
                    np.zeros((s, wide.num_blocks), np.int32),
                    np.zeros((s, wide.padded_src, wide.f), np.float32))
                jax.block_until_ready(exe(*args))

    def work(self, records) -> list:
        """(vertices, edges) of each served request's sampled subgraph."""
        return [(r.sampled_nodes, r.sampled_edges) for r in records]

    def reference(self, edges: InEdges, item: int, prec: Precision):
        nodes, src, dst = sample(edges, item, self.fanouts, self.rng_seed)
        local = np.searchsorted(nodes, [item, *src, *dst])
        out = self.arch.forward(self.host_params(),
                                self.host.features[nodes],
                                local[1: 1 + len(src)], local[1 + len(src):],
                                len(nodes), prec)
        return out[local[:1]]

    def check(self, answers, seed: int) -> dict:
        """Compare a sample of the answers, drawn from the seed, with the
        reference: the same sampling semantics and model, in float64."""
        prec = Precision("float64")
        count = min(int(self.cfg["check"]["sample"]), len(answers))
        picked = rng(seed, "check sample").choice(len(answers), count,
                                                  replace=False)
        edges = InEdges(self.src, self.dst, self.num_nodes)
        gaps = [max_error(answers[i][1],
                          self.reference(edges, answers[i][0], prec))
                for i in picked]
        result = common.verdict(self.cfg, gaps)
        result["wrong"] = [int(picked[i]) for i in result["wrong"]]
        return result

    def control(self, prec_name: str, count: int, seed: int) -> float:
        """Widest gap of the reference at ``prec_name`` over ``count``
        uniform query vertices."""
        edges = InEdges(self.src, self.dst, self.num_nodes)
        worst = 0.0
        for v in rng(seed, "control").integers(0, self.num_nodes, count):
            ref = self.reference(edges, int(v), Precision("float64"))
            low = self.reference(edges, int(v), Precision(prec_name))
            worst = max(worst, max_error(low, ref))
        return worst


def build(cfg: dict, seed: int, replicas: int) -> Deployment:
    return Deployment(cfg, seed, replicas)
