"""GraphSAGE-mean (Hamilton et al., arXiv:1706.02216): weights and plain
reference.

Layer: h'_v = W_self h_v + b + W_neigh mean_{u -> v} h_u, with ReLU between
the two layers and none after the last; a vertex with no in-edge in the
sampled subgraph has a zero mean.
"""

from __future__ import annotations

import numpy as np

from bench.precision import Precision, segment_sum

ARCH = "sage"
COMBINES_PER_LAYER = 2  # W_self h and W_neigh mean(h)


def dims(cfg: dict) -> list:
    return [(cfg["num_features"], cfg["hidden"]),
            (cfg["hidden"], cfg["num_classes"])]


def init_params(cfg: dict, key):
    import jax
    import jax.numpy as jnp

    layer_dims = dims(cfg)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, 3 * len(layer_dims))
        out = {}
        for i, (fi, fo) in enumerate(layer_dims):
            scale = (2.0 / (fi + fo)) ** 0.5
            k = keys[3 * i: 3 * i + 3]
            out[f"l{i + 1}"] = {
                "self": {"w": scale * jax.random.normal(k[0], (fi, fo),
                                                        jnp.float32),
                         "b": 0.1 * jax.random.normal(k[1], (fo,),
                                                      jnp.float32)},
                "neigh": {"w": scale * jax.random.normal(k[2], (fi, fo),
                                                         jnp.float32)}}
        return out

    return make(key)


def program_model(cfg: dict):
    from repro.gnn import build_model
    return build_model("sage", cfg["num_features"], cfg["num_classes"],
                       hidden=cfg["hidden"])


def forward(params: dict, feat: np.ndarray, src: np.ndarray, dst: np.ndarray,
            num_nodes: int, prec: Precision) -> np.ndarray:
    """[num_nodes, classes] logits of the subgraph (src -> dst edges)."""
    count = np.bincount(dst, minlength=num_nodes).astype(np.float64)
    inv = 1.0 / np.maximum(count, 1.0)
    h = np.asarray(feat, np.float64)
    for i in range(len(params)):
        p = params[f"l{i + 1}"]
        mean = prec.store(segment_sum(prec.op(h)[src], dst, num_nodes)
                          * inv[:, None])
        h = (prec.mm(h, p["self"]["w"]) + p["self"]["b"]
             + prec.mm(mean, p["neigh"]["w"]))
        if i + 1 < len(params):
            h = np.maximum(h, 0.0)
    return h
