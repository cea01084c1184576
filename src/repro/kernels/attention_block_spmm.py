"""Pallas TPU kernel: blocked GAT attention aggregation (masked edge softmax).

GHOST's GAT pipeline (paper Section 3.4.2, Fig. 6b) scores every edge,
normalizes the scores per destination vertex with a softmax and sums the
projected source features under those weights.  On the blocked adjacency
that is, per head h and destination row v:

    e_vu  = leaky_relu(s_dst[v, h] + s_src[u, h])     for tile entries u
    out_v = (sum_u c_vu exp(e_vu) val_u + exp(e_vv) val_v)
            / (sum_u c_vu exp(e_vu) + exp(e_vv))

where ``c_vu`` is the tile value (the multiplicity of edge u -> v) and the
second terms are the self term: GAT attends over N(v) u {v}.  A loop already
in the graph (a tile entry whose source is the destination itself) is not
an extra neighbour: the self term stands for it, once, whatever its
multiplicity, so such entries are masked out.  Node i is row i on both the
source and the destination side (the partition numbers both by node id).

Dataflow (the scalar-prefetch / CSR-sorted design of ``fused_block_spmm``):

* ``block_row`` / ``block_col`` are scalar-prefetched into SMEM and steer
  the DMAs of the value tile ``[N, L]`` and the source scores ``[H, N]``;
  all-zero tiles are never fetched.
* Tiles are sorted by destination row.  Consecutive grid steps of one row
  fold into an online softmax held in VMEM scratch: running max ``m``,
  running sum ``l`` and the weighted value sum ``acc``, each ``[V, L]``
  with lane j holding the state of head ``j // F`` (the accumulator's
  layout), so rescaling is one elementwise product.  The first visit to a
  row initializes the state from the row's own self term (max = its score,
  sum = 1, acc = its value); the last visit writes ``acc / l``.
* Per tile only the ``[V, N]`` logits of one head at a time exist, in
  vector registers: nothing of size ``[B, V, N, H]`` is ever built.

Rows with no tile are never visited; the wrapper in ``kernels.ops`` writes
their self value (a softmax over the self term alone).  Scores and the
projection ``W h`` are XLA matmuls outside the kernel.

Grid: (num_blocks,).  VMEM per step: adjacency tile V x N, value tile
N x L, scores H x N and V x H, self value V x L, and three V x L scratch
buffers (L = H * F padded to the 128-wide lane).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Logit of a masked entry: finite, so max/exp never meet inf - inf.
MASKED_LOGIT = -1e30


def _leaky_relu(x, negative_slope: float):
    return jnp.where(x >= 0.0, x, negative_slope * x)


def _kernel(block_row, block_col, blocks_ref, vals_ref, ssrc_ref, sdst_ref,
            sself_ref, selfval_ref, out_ref, m_ref, l_ref, acc_ref, *,
            num_blocks: int, heads: int, f: int, v: int, n: int,
            negative_slope: float):
    b = pl.program_id(0)
    row = block_row[b]
    first_visit = jnp.logical_or(
        b == 0, block_row[jnp.maximum(b, 1) - 1] != row)
    last_visit = jnp.logical_or(
        b == num_blocks - 1,
        block_row[jnp.minimum(b + 1, num_blocks - 1)] != row)

    vp, lanes = acc_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (vp, lanes), 1)
    in_head = [(lane >= h * f) & (lane < (h + 1) * f) for h in range(heads)]

    @pl.when(first_visit)
    def _init():
        self_logit = sself_ref[...]                                # [V, H]
        m0 = jnp.zeros((vp, lanes), jnp.float32)
        for h in range(heads):
            m0 = jnp.where(in_head[h], self_logit[:, h:h + 1], m0)
        m_ref[...] = m0
        l_ref[...] = jnp.ones_like(l_ref)
        acc_ref[...] = selfval_ref[...].astype(jnp.float32)

    tile = blocks_ref[...].astype(jnp.float32)                     # [V, N]
    np_ = tile.shape[1]
    dst_id = row * v + jax.lax.broadcasted_iota(jnp.int32, (vp, np_), 0)
    src_id = block_col[b] * n + jax.lax.broadcasted_iota(
        jnp.int32, (vp, np_), 1)
    edge = (tile != 0.0) & (dst_id != src_id)
    sd = sdst_ref[...].astype(jnp.float32)                         # [V, H]
    ss = ssrc_ref[...].astype(jnp.float32)                         # [H, N]
    vals = vals_ref[...].astype(jnp.float32)                       # [N, L]

    m_old = m_ref[...]
    m_new = m_old
    scale = jnp.ones((vp, lanes), jnp.float32)
    tile_sum = jnp.zeros((vp, lanes), jnp.float32)
    tile_acc = jnp.zeros((vp, lanes), jnp.float32)
    for h in range(heads):
        logit = _leaky_relu(sd[:, h:h + 1] + ss[h:h + 1, :], negative_slope)
        logit = jnp.where(edge, logit, MASKED_LOGIT)               # [V, N]
        mo = m_old[:, h * f:h * f + 1]                             # [V, 1]
        mh = jnp.maximum(mo, jnp.max(logit, axis=1, keepdims=True))
        p = jnp.where(edge, tile * jnp.exp(logit - mh), 0.0)
        pv = jnp.dot(p, vals, preferred_element_type=jnp.float32)  # [V, L]
        m_new = jnp.where(in_head[h], mh, m_new)
        scale = jnp.where(in_head[h], jnp.exp(mo - mh), scale)
        tile_sum = jnp.where(in_head[h], jnp.sum(p, axis=1, keepdims=True),
                             tile_sum)
        tile_acc = jnp.where(in_head[h], pv, tile_acc)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * scale + tile_sum
    acc_ref[...] = acc_ref[...] * scale + tile_acc

    @pl.when(last_visit)
    def _normalize():
        out_ref[...] = (acc_ref[...] / l_ref[...]).astype(out_ref.dtype)


def attention_block_spmm(
    blocks: jax.Array,       # [B, V, N] tile values (CSR-sorted by row)
    block_row: jax.Array,    # [B] int32 destination groups (non-decreasing)
    block_col: jax.Array,    # [B] int32 source groups
    values: jax.Array,       # [G_src * N, L] projected values, lane-padded
    src_scores: jax.Array,   # [G_src, H, N] source scores, per group
    dst_scores: jax.Array,   # [G_dst * V, H]
    self_logits: jax.Array,  # [G_dst * V, H] leaky_relu(s_dst + s_src) of v
    self_values: jax.Array,  # [G_dst * V, L] value of v itself, lane-padded
    num_dst_groups: int,
    heads: int,
    f: int,
    v: int,
    n: int,
    negative_slope: float = 0.2,
    interpret: bool = False,
) -> jax.Array:
    """Softmax-weighted sums of ``values`` per destination row and head.

    Returns ``[num_dst_groups * V, L]``; rows of groups with no tile are
    uninitialized (see ``ops.attention_block_spmm_padded``).  ``V``/``N``
    here are the (sublane-padded) tile dims of ``blocks``; ``v``/``n``
    are the partition's own group sizes, which number the nodes (node id
    = group * v + row), for the loop mask.
    """
    num_blocks, vp, np_ = blocks.shape
    lanes = values.shape[1]
    if values.shape[0] % np_:
        raise ValueError("value rows must be a multiple of the tile width N")
    if heads * f > lanes:
        raise ValueError(f"{heads} heads of width {f} do not fit {lanes} "
                         "lanes")
    if src_scores.shape[1:] != (heads, np_):
        raise ValueError(f"source scores {src_scores.shape} are not "
                         f"[G_src, {heads}, {np_}]")

    cost = pl.CostEstimate(
        flops=num_blocks * heads * vp * np_ * (2 * lanes + 8),
        bytes_accessed=4 * (num_blocks * (vp * np_ + np_ * lanes
                                          + heads * np_)
                            + num_dst_groups * vp * (2 * lanes + 2 * heads)),
        transcendentals=num_blocks * heads * vp * (np_ + 1),
    )
    kernel = functools.partial(_kernel, num_blocks=num_blocks, heads=heads,
                               f=f, v=v, n=n, negative_slope=negative_slope)
    row_block = lambda b, br, bc: (br[b], 0)  # noqa: E731
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(num_blocks,),
            in_specs=[
                pl.BlockSpec((None, vp, np_), lambda b, br, bc: (b, 0, 0)),
                pl.BlockSpec((np_, lanes), lambda b, br, bc: (bc[b], 0)),
                pl.BlockSpec((None, heads, np_),
                             lambda b, br, bc: (bc[b], 0, 0)),
                pl.BlockSpec((vp, heads), row_block),
                pl.BlockSpec((vp, heads), row_block),
                pl.BlockSpec((vp, lanes), row_block),
            ],
            out_specs=pl.BlockSpec((vp, lanes), row_block),
            scratch_shapes=[pltpu.VMEM((vp, lanes), jnp.float32)] * 3,
        ),
        out_shape=jax.ShapeDtypeStruct((num_dst_groups * vp, lanes),
                                       values.dtype),
        cost_estimate=cost,
        interpret=interpret,
    )(block_row, block_col, blocks, values, src_scores, dst_scores,
      self_logits, self_values)
