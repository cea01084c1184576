"""GCN (Kipf & Welling, arXiv:1609.02907): weights and plain reference.

Layer: H' = act(A_hat H W + b), A_hat = D^-1/2 (A + I) D^-1/2, with ReLU
between the two layers and none after the last.
"""

from __future__ import annotations

import numpy as np

from bench.precision import Precision, segment_sum

ARCH = "gcn"
COMBINES_PER_LAYER = 1  # H W


def dims(cfg: dict) -> list:
    """[(f_in, f_out)] per layer."""
    return [(cfg["num_features"], cfg["hidden"]),
            (cfg["hidden"], cfg["num_classes"])]


def init_params(cfg: dict, key):
    """Weights in the program's layout, made on the device in one call."""
    import jax
    import jax.numpy as jnp

    layer_dims = dims(cfg)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, 2 * len(layer_dims))
        out = {}
        for i, (fi, fo) in enumerate(layer_dims):
            scale = (2.0 / (fi + fo)) ** 0.5
            out[f"l{i + 1}"] = {
                "w": scale * jax.random.normal(keys[2 * i], (fi, fo),
                                               jnp.float32),
                "b": 0.1 * jax.random.normal(keys[2 * i + 1], (fo,),
                                             jnp.float32)}
        return out

    return make(key)


def program_model(cfg: dict):
    from repro.gnn import build_model
    return build_model("gcn", cfg["num_features"], cfg["num_classes"],
                       hidden=cfg["hidden"])


def normalized_edges(src: np.ndarray, dst: np.ndarray, num_nodes: int):
    """(src, dst, weight) of A + I with symmetric normalization; a loop is
    added to each vertex that has none."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    has_loop = np.zeros(num_nodes, bool)
    has_loop[dst[src == dst]] = True
    loops = np.flatnonzero(~has_loop)
    src = np.concatenate([src, loops])
    dst = np.concatenate([dst, loops])
    deg = np.bincount(dst, minlength=num_nodes).astype(np.float64)
    return src, dst, 1.0 / np.sqrt(deg[dst] * deg[src])


def forward(params: dict, feat: np.ndarray, edges, num_nodes: int,
            prec: Precision) -> np.ndarray:
    """[num_nodes, classes] logits; ``edges`` from ``normalized_edges``."""
    src, dst, w = edges
    h = np.asarray(feat, np.float64)
    for i in range(len(params)):
        p = params[f"l{i + 1}"]
        hw = prec.mm(h, p["w"])
        h = prec.store(segment_sum(prec.op(w)[:, None] * prec.op(hw)[src],
                                   dst, num_nodes)) + p["b"]
        if i + 1 < len(params):
            h = np.maximum(h, 0.0)
    return h
