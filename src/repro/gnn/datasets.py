"""Synthetic graph datasets matching the paper's Table 2 statistics.

The container is offline (no Planetoid/TU downloads), so we generate
synthetic datasets whose *structural statistics* match Table 2 exactly —
node/edge/feature/label/graph counts — and whose tasks are genuinely
learnable, so the fp32-vs-int8 accuracy comparison (Table 3) is meaningful:

* Node classification (Cora / PubMed / Citeseer / Amazon): degree-corrected
  stochastic block model with #labels communities and power-law degree
  propensities (citation-graph-like skew), planted class-indicative sparse
  features + noise.
* Graph classification (Proteins / Mutag / BZR / IMDB-binary): two structural
  families per dataset (ring-of-cliques vs. preferential-attachment trees)
  with class-conditional feature means.

All generation is seeded and deterministic.
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import Sequence

import numpy as np

from repro.core.graph import Graph

# Table 2 of the paper.
TABLE2 = {
    "Cora":        dict(nodes=2708, edges=10556, features=1433, labels=7, graphs=1),
    "PubMed":      dict(nodes=19717, edges=88651, features=500, labels=3, graphs=1),
    "Citeseer":    dict(nodes=3327, edges=9104, features=3703, labels=6, graphs=1),
    "Amazon":      dict(nodes=7650, edges=238162, features=745, labels=8, graphs=1),
    "Proteins":    dict(nodes=39, edges=73, features=3, labels=2, graphs=1113),
    "Mutag":       dict(nodes=18, edges=40, features=143, labels=2, graphs=188),
    "BZR":         dict(nodes=34, edges=38, features=189, labels=2, graphs=405),
    "IMDB-binary": dict(nodes=20, edges=193, features=136, labels=2, graphs=1000),
}

NODE_CLASSIFICATION = ("Cora", "PubMed", "Citeseer", "Amazon")
GRAPH_CLASSIFICATION = ("Proteins", "Mutag", "BZR", "IMDB-binary")


def _name_seed(name: str, seed: int) -> int:
    """Per-dataset rng seed.  ``zlib.crc32``, not ``hash``: Python salts
    str hashes per process, which made every process draw other graphs."""
    return seed + zlib.crc32(name.encode()) % 65536


def _planted_features(rng, labels, num_features, signal=1.0, noise=1.0,
                      sparsity=0.05):
    """Sparse class-prototype features + Gaussian noise (bag-of-words-like)."""
    num_classes = labels.max() + 1
    proto = (rng.random((num_classes, num_features)) < sparsity).astype(np.float32)
    feat = signal * proto[labels]
    feat += noise * rng.standard_normal(feat.shape).astype(np.float32) * 0.3
    # Word-count-like nonnegativity, matching the citation datasets.
    return np.maximum(feat, 0.0)


def _dc_sbm_edges(rng, labels, num_edges, p_in=0.85):
    """Degree-corrected SBM: sample directed edge endpoints until we have
    ``num_edges`` unique non-self edges; intra-class with prob p_in."""
    n = len(labels)
    num_classes = labels.max() + 1
    # Power-law degree propensity (citation skew).
    theta = rng.pareto(2.5, size=n) + 1.0
    by_class = [np.where(labels == c)[0] for c in range(num_classes)]
    probs = [theta[idx] / theta[idx].sum() for idx in by_class]
    theta_all = theta / theta.sum()

    edges = set()
    batch = max(num_edges, 1024)
    while len(edges) < num_edges:
        src = rng.choice(n, size=batch, p=theta_all)
        intra = rng.random(batch) < p_in
        dst = np.empty(batch, dtype=np.int64)
        for c in range(num_classes):
            m = intra & (labels[src] == c)
            if m.any():
                dst[m] = rng.choice(by_class[c], size=int(m.sum()), p=probs[c])
        m = ~intra
        if m.any():
            dst[m] = rng.choice(n, size=int(m.sum()), p=theta_all)
        for s, d in zip(src.tolist(), dst.tolist()):
            if s != d:
                edges.add((s, d))
                if len(edges) >= num_edges:
                    break
    arr = np.array(sorted(edges), dtype=np.int32)
    return arr[:, 0], arr[:, 1]


def make_node_classification(name: str, seed: int = 0) -> Graph:
    spec = TABLE2[name]
    rng = np.random.default_rng(_name_seed(name, seed))
    n, e = spec["nodes"], spec["edges"]
    labels = rng.integers(0, spec["labels"], size=n).astype(np.int32)
    src, dst = _dc_sbm_edges(rng, labels, e)
    feat = _planted_features(rng, labels, spec["features"])

    idx = rng.permutation(n)
    n_train = max(int(0.6 * n), spec["labels"] * 20)
    n_val = int(0.2 * n)
    train_mask = np.zeros(n, bool); train_mask[idx[:n_train]] = True
    val_mask = np.zeros(n, bool); val_mask[idx[n_train:n_train + n_val]] = True
    test_mask = np.zeros(n, bool); test_mask[idx[n_train + n_val:]] = True

    return Graph(
        edge_src=src, edge_dst=dst, node_feat=feat, labels=labels,
        train_mask=train_mask, val_mask=val_mask, test_mask=test_mask,
        name=name,
    ).validate()


def _ring_of_cliques(rng, n):
    """Class-0 structure: small cliques chained in a ring (high clustering)."""
    edges = set()
    k = max(3, n // 6)
    for start in range(0, n - k + 1, k):
        members = range(start, min(start + k, n))
        for a in members:
            for b in members:
                if a < b:
                    edges.add((a, b))
    for i in range(n):
        edges.add((i, (i + 1) % n))
    return edges


def _pa_tree(rng, n, extra=2):
    """Class-1 structure: preferential-attachment tree + a few chords (low
    clustering, skewed degrees)."""
    edges = set()
    targets = [0]
    for i in range(1, n):
        j = int(rng.choice(targets))
        edges.add((min(i, j), max(i, j)))
        targets += [i, j]
    for _ in range(extra):
        a, b = rng.integers(0, n, 2)
        if a != b:
            edges.add((min(a, b), max(a, b)))
    return edges


def make_graph_classification(name: str, seed: int = 0,
                              num_graphs: int | None = None) -> list[Graph]:
    spec = TABLE2[name]
    rng = np.random.default_rng(_name_seed(name, seed))
    count = num_graphs or spec["graphs"]
    avg_n, avg_e, f = spec["nodes"], spec["edges"], spec["features"]
    graphs = []
    for gi in range(count):
        label = gi % 2
        n = max(4, int(rng.normal(avg_n, max(avg_n * 0.15, 1))))
        und = _ring_of_cliques(rng, n) if label == 0 else _pa_tree(rng, n)
        und = list(und)
        rng.shuffle(und)
        # Trim/keep to track the average undirected edge count.
        target_und = max(n - 1, int(rng.normal(avg_e, max(avg_e * 0.1, 1))) // 2)
        und = und[:max(target_und, n // 2)]
        src = np.array([a for a, b in und] + [b for a, b in und], np.int32)
        dst = np.array([b for a, b in und] + [a for a, b in und], np.int32)
        base = rng.standard_normal((n, f)).astype(np.float32) * 0.5
        base += (0.6 if label == 1 else -0.6) * np.linspace(1, 0, f, dtype=np.float32)
        deg = np.zeros(n, np.float32)
        np.add.at(deg, dst, 1.0)
        base[:, 0] = deg / max(deg.max(), 1.0)  # degree feature helps both classes
        graphs.append(Graph(
            edge_src=src, edge_dst=dst, node_feat=base,
            graph_label=label, name=f"{name}[{gi}]",
        ).validate())
    return graphs


def load(name: str, seed: int = 0, num_graphs: int | None = None):
    """Load a synthetic Table-2 dataset by name.

    Node-classification names return a single Graph; graph-classification
    names return a list of Graphs.
    """
    if name in NODE_CLASSIFICATION:
        return make_node_classification(name, seed)
    if name in GRAPH_CLASSIFICATION:
        return make_graph_classification(name, seed, num_graphs)
    raise KeyError(f"unknown dataset '{name}'; options: {sorted(TABLE2)}")
