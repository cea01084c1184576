"""Plain k-hop fanout sampling, for the reference of node queries.

The semantics the served sampler states (``sample_khop`` in
``src/repro/serving/sampler.py``), written again on the benchmark's own
in-edge lists: hop ``l`` takes, for each vertex first reached at hop
``l - 1``, all its in-neighbours when it has at most ``fanouts[l-1]`` of
them, and otherwise those at the positions that
``numpy.random.default_rng((rng_seed, v)).choice(degree, fanout,
replace=False)`` draws from its in-neighbours sorted by source id.  The
sources reached join the vertex set, and the new ones are the next
frontier.
"""

from __future__ import annotations

import numpy as np


class InEdges:
    """In-neighbour lists of a graph, sorted by source id per vertex."""

    def __init__(self, src: np.ndarray, dst: np.ndarray, num_nodes: int):
        key = np.asarray(dst, np.int64) * num_nodes + np.asarray(src, np.int64)
        key.sort()
        self.indices = key % num_nodes
        self.indptr = np.searchsorted(key // num_nodes,
                                      np.arange(num_nodes + 1))

    def of(self, v: int) -> np.ndarray:
        return self.indices[self.indptr[v]: self.indptr[v + 1]]


def sample(edges: InEdges, seed: int, fanouts, rng_seed: int):
    """(nodes, src, dst): sorted host ids of the sampled vertices and the
    sampled edges (host ids, src -> dst)."""
    nodes = {int(seed)}
    frontier = [int(seed)]
    src_parts, dst_parts = [], []
    for fanout in fanouts:
        reached = []
        for v in frontier:
            nb = edges.of(v)
            if fanout is not None and nb.size > fanout:
                pick = np.random.default_rng((rng_seed, v)).choice(
                    nb.size, size=fanout, replace=False)
                nb = nb[pick]
            src_parts.append(nb)
            dst_parts.append(np.full(nb.size, v, np.int64))
            reached.extend(nb.tolist())
        frontier = sorted(set(reached) - nodes)
        nodes.update(frontier)
    src = np.concatenate(src_parts) if src_parts else np.zeros(0, np.int64)
    dst = np.concatenate(dst_parts) if dst_parts else np.zeros(0, np.int64)
    return np.array(sorted(nodes), np.int64), src, dst
