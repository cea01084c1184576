"""Seeded graph generators of the benchmark's deployments.

Copies of the program's generators, kept here so that a change to the
program cannot change the data it is measured on:

- ``cora_structure`` copies ``_dc_sbm_edges`` of ``src/repro/gnn/datasets.py``
  (degree-corrected stochastic block model with power-law propensities);
- ``power_law_edges`` copies the edge draw of
  ``HostGraph.synthetic_power_law`` in ``src/repro/serving/sampler.py``
  (uniform destinations, Zipf-like sources over a random permutation).

Features are drawn here as float32 (the program's generator draws float64
and casts, which takes most of its time at Reddit's size).
"""

from __future__ import annotations

import numpy as np


def cora_structure(num_nodes: int, num_edges: int, num_classes: int,
                   seed: int, p_in: float = 0.85):
    """Directed edge list (src, dst) int32 of ``num_edges`` unique non-self
    edges over ``num_nodes`` vertices in ``num_classes`` communities."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, num_classes, size=num_nodes)
    theta = rng.pareto(2.5, size=num_nodes) + 1.0
    by_class = [np.flatnonzero(labels == c) for c in range(num_classes)]
    probs = [theta[idx] / theta[idx].sum() for idx in by_class]
    theta_all = theta / theta.sum()
    edges: set = set()
    batch = max(num_edges, 1024)
    while len(edges) < num_edges:
        src = rng.choice(num_nodes, size=batch, p=theta_all)
        intra = rng.random(batch) < p_in
        dst = np.empty(batch, dtype=np.int64)
        for c in range(num_classes):
            m = intra & (labels[src] == c)
            if m.any():
                dst[m] = rng.choice(by_class[c], size=int(m.sum()), p=probs[c])
        m = ~intra
        if m.any():
            dst[m] = rng.choice(num_nodes, size=int(m.sum()), p=theta_all)
        for s, d in zip(src.tolist(), dst.tolist()):
            if s != d:
                edges.add((s, d))
                if len(edges) >= num_edges:
                    break
    arr = np.array(sorted(edges), dtype=np.int32)
    return arr[:, 0], arr[:, 1]


def power_law_edges(num_nodes: int, avg_degree: int, seed: int,
                    exponent: float = 1.1):
    """(src, dst) int64 of ``num_nodes * avg_degree`` edges: destinations
    uniform, sources Zipf(``exponent``) over a seeded permutation."""
    rng = np.random.default_rng(seed)
    num_edges = num_nodes * avg_degree
    p = np.arange(1, num_nodes + 1, dtype=np.float64) ** (-exponent)
    p /= p.sum()
    perm = rng.permutation(num_nodes)
    src = perm[rng.choice(num_nodes, size=num_edges, p=p)]
    dst = rng.integers(0, num_nodes, num_edges)
    return src.astype(np.int64), dst.astype(np.int64)


def float_features(rows: int, cols: int, seed: int) -> np.ndarray:
    """[rows, cols] float32 standard normal features."""
    return np.random.default_rng(seed).standard_normal((rows, cols),
                                                       dtype=np.float32)


def binary_features(rows: int, cols: int, density: float,
                    rng: np.random.Generator) -> np.ndarray:
    """[rows, cols] float32 0/1 bag-of-words features at ``density``."""
    return (rng.random((rows, cols), dtype=np.float32) < density).astype(
        np.float32)
