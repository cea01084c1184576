"""GNN layers built on the GReTA decomposition (gather/reduce/transform/activate).

Each conv exposes the two execution backends:

  apply         — edge-list backend (training / oracle)
  apply_blocked — GHOST V x N blocked backend (serving)

and an optional quantized combine (the photonic 8-bit sign-split MVM).  The
two backends compute the same function in float32, summed in another order,
so they agree to a tolerance of the output's scale, not bit for bit: a few
ULP on the CPU, and on the TPU, where a float32 matmul is one bfloat16
pass, that pass's rounding.

Every ``apply_blocked`` aggregate+combine pair routes through
``core.aggregate.aggregate_combine_blocked``: the static order planner
picks aggregate-first vs combine-first per layer, and the ``pallas_fused``
serving backend lowers the aggregate-first order onto the fused SpMM+combine
epilogue kernel.  GAT is transform-first by construction; its projection is
the same ``dense_combine`` map the planner's combine-first leg uses.
Quantized combines stay on the unfused aggregate-then-int8-MVM path — the
sign-split quantizer is nonlinear, so reordering around it would change the
served numerics.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.aggregate import (
    BlockedGraph,
    ReduceOp,
    active_aggregate_backend,
    aggregate_blocked,
    aggregate_combine_blocked,
    aggregate_edges,
    attention_aggregate_blocked,
    dense_combine,
    to_dst_rows,
)


def init_linear(key, f_in: int, f_out: int, bias: bool = True) -> dict:
    wkey, _ = jax.random.split(key)
    scale = (2.0 / (f_in + f_out)) ** 0.5
    p = {"w": scale * jax.random.normal(wkey, (f_in, f_out), jnp.float32)}
    if bias:
        p["b"] = jnp.zeros((f_out,), jnp.float32)
    return p


def _linear(x, p, quantized: bool):
    return dense_combine(x, p["w"], p.get("b"), quantized=quantized)


# ---------------------------------------------------------------------------
# GCN (Kipf & Welling) — aggregate(sum, Â) -> transform -> activate.
# ---------------------------------------------------------------------------


class GCNConv:
    @staticmethod
    def init(key, f_in, f_out):
        return init_linear(key, f_in, f_out)

    @staticmethod
    def apply(p, feat, edge_src, edge_dst, edge_weight, num_nodes,
              quantized=False):
        h = aggregate_edges(edge_src, edge_dst, feat, num_nodes,
                            ReduceOp.SUM, edge_weight)
        return _linear(h, p, quantized)

    @staticmethod
    def apply_blocked(p, bg: BlockedGraph, feat_padded, quantized=False):
        # GCN normalization is baked into the partition blocks; the whole
        # layer is one planner-ordered (and optionally fused) stage pair.
        return aggregate_combine_blocked(
            bg, feat_padded, p["w"], p.get("b"), reduce=ReduceOp.SUM,
            quantized=quantized)


# ---------------------------------------------------------------------------
# GraphSAGE (mean) — h' = W_self h + W_neigh mean(h_u).
# ---------------------------------------------------------------------------


class SAGEConv:
    @staticmethod
    def init(key, f_in, f_out):
        k1, k2 = jax.random.split(key)
        return {"self": init_linear(k1, f_in, f_out),
                "neigh": init_linear(k2, f_in, f_out, bias=False)}

    @staticmethod
    def apply(p, feat, edge_src, edge_dst, edge_weight, num_nodes,
              quantized=False):
        h = aggregate_edges(edge_src, edge_dst, feat, num_nodes, ReduceOp.MEAN)
        return _linear(feat, p["self"], quantized) + _linear(h, p["neigh"], quantized)

    @staticmethod
    def apply_blocked(p, bg: BlockedGraph, feat_padded, quantized=False):
        # Neighbor term = MEAN-aggregate fused with the (bias-free) W_neigh
        # combine; the self term stays a plain dense map on its own rows.
        h = aggregate_combine_blocked(
            bg, feat_padded, p["neigh"]["w"], reduce=ReduceOp.MEAN,
            quantized=quantized)
        self_feat = to_dst_rows(feat_padded, bg.num_dst_groups * bg.v)
        return _linear(self_feat, p["self"], quantized) + h


# ---------------------------------------------------------------------------
# GIN — h' = MLP((1 + eps) h + sum(h_u)).
# ---------------------------------------------------------------------------


class GINConv:
    @staticmethod
    def init(key, f_in, f_out, mlp_layers=4, hidden=None):
        hidden = hidden or f_out
        keys = jax.random.split(key, mlp_layers)
        dims = [f_in] + [hidden] * (mlp_layers - 1) + [f_out]
        mlp = [init_linear(k, dims[i], dims[i + 1]) for i, k in enumerate(keys)]
        return {"eps": jnp.zeros(()), "mlp": mlp}

    @staticmethod
    def _mlp(p, x, quantized):
        for i, layer in enumerate(p["mlp"]):
            x = _linear(x, layer, quantized)
            if i + 1 < len(p["mlp"]):
                x = jax.nn.relu(x)
        return x

    @staticmethod
    def apply(p, feat, edge_src, edge_dst, edge_weight, num_nodes,
              quantized=False):
        h = aggregate_edges(edge_src, edge_dst, feat, num_nodes, ReduceOp.SUM)
        return GINConv._mlp(p, (1.0 + p["eps"]) * feat + h, quantized)

    @staticmethod
    def apply_blocked(p, bg: BlockedGraph, feat_padded, quantized=False):
        self_feat = to_dst_rows(feat_padded, bg.num_dst_groups * bg.v)
        if quantized or active_aggregate_backend() != "pallas_fused":
            # Unfused form.  Quantized: the int8 MVM quantizes its input, so
            # W1 cannot be distributed over the (self, aggregate) sum
            # without changing numerics.  jnp/pallas: distributing W1 buys
            # nothing without the fused epilogue, and keeping the seed
            # association preserves the engine's batched-vs-unbatched
            # bit-exactness for GIN's magnitude-amplifying sum-pool readout.
            h = aggregate_blocked(bg, feat_padded, ReduceOp.SUM)
            return GINConv._mlp(p, (1.0 + p["eps"]) * self_feat + h, quantized)
        # Distribute the first MLP layer over the sum so its combine fuses
        # with the aggregation:  ((1+eps)x + h) W1 + b1
        #                     == (1+eps)(x W1) + (h W1) + b1.
        mlp0 = p["mlp"][0]
        h_w = aggregate_combine_blocked(bg, feat_padded, mlp0["w"],
                                        reduce=ReduceOp.SUM)
        x = (1.0 + p["eps"]) * (self_feat @ mlp0["w"]) + h_w
        if "b" in mlp0:
            x = x + mlp0["b"]
        for layer in p["mlp"][1:]:
            x = _linear(jax.nn.relu(x), layer, quantized)
        return x


# ---------------------------------------------------------------------------
# GAT — transform-first: e_uv = leaky_relu(a . [W h_v || W h_u]), softmax
# over N(v) u {v}, weighted sum.  Multi-head with concat (hidden layers) or
# mean (output).
# ---------------------------------------------------------------------------


class GATConv:
    """One GAT layer (Velickovic et al., arXiv:1710.10903, eq. 1-4).

    Each vertex attends over its in-neighbours and itself, N(v) u {v}, as
    published.  A duplicate edge weighs as many times as it appears.  A loop
    already in the graph is not an extra neighbour: the self term stands for
    it, once, so loops in the edge list (or tile entries whose source is the
    destination) are ignored.  ``apply`` (float32 edge list) is the oracle of
    ``apply_blocked``, which runs the blocked edge-softmax kernel on the
    Pallas backends (``core.aggregate.attention_aggregate_blocked``).
    """

    @staticmethod
    def init(key, f_in, f_out, heads=1):
        k1, k2, k3 = jax.random.split(key, 3)
        scale = (2.0 / (f_in + f_out)) ** 0.5
        return {
            "w": scale * jax.random.normal(k1, (f_in, heads, f_out)),
            "a_src": 0.1 * jax.random.normal(k2, (heads, f_out)),
            "a_dst": 0.1 * jax.random.normal(k3, (heads, f_out)),
            "b": jnp.zeros((heads, f_out)),
        }

    @staticmethod
    def _project(p, feat, quantized):
        # GAT is transform-first (paper Section 3.4.2): the projection IS
        # the combine-first order, so it runs through the shared combine map
        # rather than a private matmul.
        heads, f_out = p["a_src"].shape
        w2d = p["w"].reshape(feat.shape[-1], heads * f_out)
        wh = dense_combine(feat, w2d, quantized=quantized)
        return wh.reshape(feat.shape[0], heads, f_out)

    @staticmethod
    def apply(p, feat, edge_src, edge_dst, edge_weight, num_nodes,
              quantized=False, concat=True, negative_slope=0.2):
        wh = GATConv._project(p, feat, quantized)                # [N,H,F]
        s_src = (wh * p["a_src"]).sum(-1)                        # [N,H]
        s_dst = (wh * p["a_dst"]).sum(-1)
        edge = (edge_src != edge_dst)[:, None]                   # loops out
        logits = jax.nn.leaky_relu(
            s_dst[edge_dst] + s_src[edge_src], negative_slope
        )                                                        # [E,H]
        logits = jnp.where(edge, logits, -1e30)
        self_logit = jax.nn.leaky_relu(s_dst + s_src, negative_slope)
        m = jax.ops.segment_max(logits, edge_dst, num_segments=num_nodes)
        m = jnp.maximum(m, self_logit)                           # [N,H]
        z = jnp.where(edge, jnp.exp(logits - m[edge_dst]), 0.0)
        z_self = jnp.exp(self_logit - m)
        denom = jax.ops.segment_sum(z, edge_dst, num_segments=num_nodes)
        denom = denom + z_self
        msgs = z[..., None] * wh[edge_src]                       # [E,H,F]
        out = jax.ops.segment_sum(msgs, edge_dst, num_segments=num_nodes)
        out = (out + z_self[..., None] * wh) / denom[..., None]
        out = out + p["b"]
        if concat:
            return out.reshape(num_nodes, -1)
        return out.mean(axis=1)

    @staticmethod
    def apply_blocked(p, bg: BlockedGraph, feat_padded, quantized=False,
                      concat=True, negative_slope=0.2):
        wh = GATConv._project(p, feat_padded, quantized)         # [Npad,H,F]
        s_src = (wh * p["a_src"]).sum(-1)
        s_dst = (wh * p["a_dst"]).sum(-1)
        pad_dst = bg.num_dst_groups * bg.v
        out = attention_aggregate_blocked(
            bg, wh, s_src, to_dst_rows(s_dst, pad_dst), negative_slope
        )                                                        # [pad_dst,H,F]
        out = out + p["b"]
        if concat:
            return out.reshape(pad_dst, -1)
        return out.mean(axis=1)
