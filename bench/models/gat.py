"""GAT (Velickovic et al., arXiv:1710.10903): weights, plain reference and
the count of its attention work.

Layer (eq. 1-4, section 3.3's transductive setup): per head k,
    e_ij = LeakyReLU_0.2(a_dst . W h_i + a_src . W h_j),
    alpha_ij = softmax_j over N(i) u {i},
    h'_i = sum_j alpha_ij W h_j + b,
concatenated over the K heads and followed by ELU in the hidden layer, and
averaged over heads (one head here) in the output layer.  A loop already in
the graph is not an extra neighbour: the self term stands for it, once.
"""

from __future__ import annotations

import numpy as np

from bench.precision import Precision, segment_sum

ARCH = "gat"
COMBINES_PER_LAYER = 1  # W h; the scores a . W h are counted in attention
NEGATIVE_SLOPE = 0.2


def dims(cfg: dict) -> list:
    """[(f_in, f_out)] per layer, f_out over all heads."""
    width = cfg["hidden"] * cfg["heads"]
    return [(cfg["num_features"], width), (width, cfg["num_classes"])]


def head_shapes(layer_dims, heads: int) -> list:
    """[(H, F)] per layer: ``heads`` heads of f_out / heads in the hidden
    layers, one head of f_out in the output layer."""
    last = len(layer_dims) - 1
    return [(1, fo) if i == last else (heads, fo // heads)
            for i, (_, fo) in enumerate(layer_dims)]


def init_params(cfg: dict, key):
    """Weights in the program's layout (``w [f_in, H, F]``, ``a_src``,
    ``a_dst``, ``b [H, F]``), made on the device in one call."""
    import jax
    import jax.numpy as jnp

    layer_dims = dims(cfg)
    shapes = head_shapes(layer_dims, cfg["heads"])

    @jax.jit
    def make(key):
        keys = jax.random.split(key, 4 * len(layer_dims))
        out = {}
        for i, ((fi, fo), (h, f)) in enumerate(zip(layer_dims, shapes)):
            k = keys[4 * i: 4 * i + 4]
            scale = (2.0 / (fi + fo)) ** 0.5  # Glorot over W [f_in, H*F]
            out[f"l{i + 1}"] = {
                "w": scale * jax.random.normal(k[0], (fi, h, f), jnp.float32),
                "a_src": 0.1 * jax.random.normal(k[1], (h, f), jnp.float32),
                "a_dst": 0.1 * jax.random.normal(k[2], (h, f), jnp.float32),
                "b": 0.1 * jax.random.normal(k[3], (h, f), jnp.float32)}
        return out

    return make(key)


def program_model(cfg: dict):
    """The program's GAT at the configuration's widths.

    Fails at once where the program's GAT does not attend over the self
    term, as this reference does: every answer would then be wrong, and
    the run would say so only after its window.
    """
    import jax
    import jax.numpy as jnp

    from repro.gnn import build_model

    probe = build_model("gat", 2, 1, hidden=2, heads=1)
    params = probe.init(jax.random.PRNGKey(0))
    none = jnp.zeros((0,), jnp.int32)
    alone = probe.apply(params, jnp.ones((1, 2), jnp.float32), none, none,
                        None, 1)
    if np.allclose(np.asarray(alone), 0.0):
        raise RuntimeError("the program's GAT gives a vertex with no edge "
                           "nothing of its own: it does not attend over "
                           "N(v) u {v}, which this reference does")
    return build_model("gat", cfg["num_features"], cfg["num_classes"],
                       hidden=cfg["hidden"], heads=cfg["heads"])


def normalized_edges(src: np.ndarray, dst: np.ndarray, num_nodes: int):
    """(src, dst, weight) of the entries GAT attends over: the edges that
    are not loops, each with its multiplicity, and one self term per
    vertex, all of weight 1."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    keep = src != dst
    nodes = np.arange(num_nodes)
    src = np.concatenate([src[keep], nodes])
    dst = np.concatenate([dst[keep], nodes])
    return src, dst, np.ones(len(src))


def forward(params: dict, feat: np.ndarray, edges, num_nodes: int,
            prec: Precision) -> np.ndarray:
    """[num_nodes, classes] logits; ``edges`` from ``normalized_edges``."""
    src, dst, w = edges
    h = np.asarray(feat, np.float64)
    layers = len(params)
    for i in range(layers):
        p = params[f"l{i + 1}"]
        fi, heads, f = p["w"].shape
        wh = prec.mm(h, p["w"].reshape(fi, heads * f)).reshape(-1, heads, f)
        s_src = prec.store((wh * p["a_src"]).sum(-1))               # [V, H]
        s_dst = prec.store((wh * p["a_dst"]).sum(-1))
        e = s_dst[dst] + s_src[src]                                 # [E, H]
        e = np.where(e >= 0.0, e, NEGATIVE_SLOPE * e)
        m = np.full((num_nodes, heads), -np.inf)
        np.maximum.at(m, dst, e)
        z = w[:, None] * np.exp(e - m[dst])
        alpha = z / segment_sum(z, dst, num_nodes)[dst]
        out = prec.store(segment_sum(prec.op(alpha)[..., None]
                                     * prec.op(wh)[src], dst, num_nodes))
        out = out + p["b"]
        if i + 1 < layers:
            out = out.reshape(num_nodes, heads * f)
            h = np.where(out > 0.0, out, np.expm1(np.minimum(out, 0.0)))
        else:
            h = out.mean(axis=1)
    return h


def attention_work(shapes, vertices: int, entries: int) -> tuple:
    """(FLOPs, bytes) of every layer's attention on a graph of ``vertices``
    vertices and ``entries`` attended entries (edges plus self terms), at
    ``shapes`` = [(H, F)] per layer: the least any kernel must do.

    Per entry and head: the score sum, LeakyReLU (2), the max subtracted,
    the exponential and its sum (6), and the weighted value sum (2F).  It
    reads 4 bytes per entry (its index), the F-wide values and two scores
    of every vertex per head, and writes the F-wide output of each.
    """
    flops = nbytes = 0
    for heads, f in shapes:
        flops += entries * heads * (2 * f + 6)
        nbytes += 4 * entries + 4 * vertices * heads * (2 * f + 2)
    return float(flops), float(nbytes)
