"""Whole-graph inference: every request is one graph, answered in full.

The configuration fixes one graph structure (a seeded generator, like a
dataset); each request carries that structure with a feature matrix of
its own, drawn from a pool that the run's seed fills and the traffic
cycles through.  So after the first request the program's partition cache
holds the structure, and host stacking, the copy to the device and the
kernels do the work.
"""

from __future__ import annotations

import numpy as np

from bench import graphs
from bench.kinds import common
from bench.precision import Precision, max_error
from bench.spec import load_module, rng



class Deployment(common.Deployment):
    def __init__(self, cfg: dict, seed: int, replicas: int):
        self.cfg = cfg
        self.arch = load_module("models", cfg["arch"])
        g = cfg["graph"]
        if g["generator"] != "cora_dc_sbm":
            raise KeyError(f"unknown graph generator {g['generator']!r}")
        self.num_nodes = int(g["num_nodes"])
        self.src, self.dst = graphs.cora_structure(
            self.num_nodes, int(g["num_edges"]), int(g["communities"]),
            seed=int(g["structure_seed"]))
        feats = cfg["features"]
        r = rng(seed, "features")
        self.pool = [graphs.binary_features(self.num_nodes,
                                            cfg["num_features"],
                                            feats["density"], r)
                     for _ in range(int(cfg["request_pool"]))]
        self.warm_feat = graphs.binary_features(
            self.num_nodes, cfg["num_features"], feats["density"],
            rng(seed, "warm features"))
        super().__init__(cfg, seed, replicas)
        from repro.serving import gcn_prepare
        prepare = {"gcn": gcn_prepare}.get(cfg["arch"])
        self.register(task="node", prepare_fn=prepare)

    def _graph(self, feat):
        from repro.core import Graph
        return Graph(edge_src=self.src, edge_dst=self.dst, node_feat=feat)

    def items(self, traffic: dict, count: int, r: np.random.Generator):
        """``count`` requests: pool indices, cycled from a seeded start."""
        start = int(r.integers(0, len(self.pool)))
        return [(start + i) % len(self.pool) for i in range(count)]

    def submit(self, item):
        return self.server.try_submit(
            common.MODEL_ID, self._graph(self.pool[item]))

    def warm_up(self, seed: int) -> None:
        """Full batches on every replica, with features of their own."""
        for engine in self.engines:
            engine.start()
            try:
                rids = [engine.try_submit(common.MODEL_ID,
                                          self._graph(self.warm_feat))
                        for _ in range(2 * engine.slots)]
                for rid in rids:
                    engine.result(rid, timeout=common.WARM_TIMEOUT_S)
            finally:
                engine.stop(drain=True)

    def work(self, records) -> list:
        """(vertices, edges) each served request ran on: the graph with
        the loops that GCN's normalization adds."""
        edges = len(self.src) + self.num_nodes
        return [(self.num_nodes, edges)] * len(records)

    def check(self, answers, seed: int) -> dict:
        """Compare every answer with the reference of its feature matrix.

        ``answers`` is [(item, output)]; returns the widest gap, the
        number checked, and the indices of answers over the limit.
        """
        prec = Precision("float64")
        edges = self.arch.normalized_edges(self.src, self.dst, self.num_nodes)
        params = self.host_params()
        refs = {}
        for item in sorted({item for item, _ in answers}):
            refs[item] = self.arch.forward(params, self.pool[item], edges,
                                           self.num_nodes, prec)
        gaps = [max_error(out, refs[item]) for item, out in answers]
        return common.verdict(self.cfg, gaps)

    def control(self, prec_name: str, count: int, seed: int = 0) -> float:
        """Widest gap of the reference at ``prec_name`` on ``count`` pool
        entries: what a program computing at that precision would read."""
        edges = self.arch.normalized_edges(self.src, self.dst, self.num_nodes)
        params = self.host_params()
        worst = 0.0
        for item in range(min(count, len(self.pool))):
            ref = self.arch.forward(params, self.pool[item], edges,
                                    self.num_nodes, Precision("float64"))
            low = self.arch.forward(params, self.pool[item], edges,
                                    self.num_nodes, Precision(prec_name))
            worst = max(worst, max_error(low, ref))
        return worst


def build(cfg: dict, seed: int, replicas: int) -> Deployment:
    return Deployment(cfg, seed, replicas)

