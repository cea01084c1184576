"""Jit'd public wrappers around the Pallas kernels.

Off the TPU the kernels run in ``interpret=True`` mode — the kernel body
executes in Python with the same block/grid semantics, which is how
correctness is validated on a CPU.  On TPU backends the compiled kernels
run natively.  ``auto_interpret()`` picks per backend; code that must prove
it ran on the chip checks the compiled program for ``tpu_custom_call``
(see ``chip_smoke.py``).

The wrappers also handle padding to tile multiples so callers can pass
arbitrary shapes: feature and weight dims to the 128-wide lane, and the
tile dims V and N to the 8-row sublane (``SUBLANE``).  The TPU lowering
refuses a block whose second-minor dim is not a multiple of 8, and the
adjacency tile's V and N are exactly such dims of the feature, degree and
output blocks.  Padded tile rows and columns are zero, which adds nothing
to SUM/MEAN and means "no edge" to MAX, and the padded output rows are
sliced off again.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.aggregate import to_dst_rows
from repro.core.partition import PartitionedGraph
from repro.kernels.attention_block_spmm import attention_block_spmm
from repro.kernels.block_spmm import block_spmm
from repro.kernels.fused_block_spmm import (
    apply_epilogue_activation,
    fused_block_spmm,
)
from repro.kernels.quant_matmul import quant_matmul
from repro.kernels import ref
from repro.photonic.quant import QuantConfig, compute_scale, quantize, quantize_weights


def auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


# Second-minor (sublane) multiple the TPU lowering requires of a block.
SUBLANE = 8


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pad_groups(x: jax.Array, group: int) -> jax.Array:
    """[G*group, ...] -> [G*group8, ...]: zero rows appended to every group
    of ``group`` rows, so each group fills a whole number of sublanes."""
    if group % SUBLANE == 0:
        return x
    g = x.reshape(-1, group, *x.shape[1:])
    return _pad_to(g, 1, SUBLANE).reshape(-1, *x.shape[1:])


def _unpad_groups(x: jax.Array, group: int) -> jax.Array:
    """Inverse of ``_pad_groups``: keep the first ``group`` rows of each
    sublane-padded group."""
    if group % SUBLANE == 0:
        return x
    padded = group + (-group) % SUBLANE
    g = x.reshape(-1, padded, *x.shape[1:])[:, :group]
    return g.reshape(-1, *x.shape[1:])


def _pad_tiles(blocks: jax.Array) -> jax.Array:
    """[B, V, N] -> [B, V8, N8] with zero rows and columns."""
    return _pad_to(_pad_to(blocks, 1, SUBLANE), 2, SUBLANE)


@functools.partial(jax.jit, static_argnames=("num_dst_groups", "block_f", "interpret"))
def block_spmm_padded(
    blocks: jax.Array,
    block_row: jax.Array,
    block_col: jax.Array,
    feat: jax.Array,
    num_dst_groups: int,
    block_f: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """block_spmm with feature-dim and tile padding.  Returns [G_dst*V, F]."""
    interpret = auto_interpret() if interpret is None else interpret
    f = feat.shape[1]
    v, n = blocks.shape[1:]
    featp = _pad_groups(_pad_to(feat, 1, block_f), n)
    out = block_spmm(
        _pad_tiles(blocks), block_row, block_col, featp, num_dst_groups,
        block_f=block_f, interpret=interpret,
    )
    out = _unpad_groups(out, v)
    # Destination groups with no tiles are never visited by the kernel, so
    # their output blocks are uninitialized; zero them here.
    visited = jnp.zeros((num_dst_groups,), jnp.bool_).at[block_row].set(True)
    out = jnp.where(jnp.repeat(visited, v)[:, None], out, 0.0)
    return out[:, :f]


@functools.partial(
    jax.jit,
    static_argnames=("num_dst_groups", "activation", "reduce", "quantized",
                     "lane", "interpret"),
)
def fused_block_spmm_padded(
    blocks: jax.Array,          # [B, V, N] CSR-row-sorted tiles
    block_row: jax.Array,       # [B] int32, non-decreasing
    block_col: jax.Array,       # [B] int32
    feat: jax.Array,            # [G_src * N, F_in]
    w: jax.Array,               # [F_in, F_out] float weights
    bias: jax.Array | None,     # [F_out] or None
    inv_deg: jax.Array | None,  # [G_dst * V] inverse degrees (MEAN) or None
    num_dst_groups: int,
    activation: str = "none",
    reduce: str = "sum",
    quantized: bool = False,
    lane: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """fused_block_spmm with lane and tile padding + unvisited-row patch-up.

    Pads F_in/F_out to ``lane`` multiples (zero feature columns x zero
    weight rows contribute nothing; padded output columns are sliced off)
    and V/N to sublane multiples (zero tile rows/columns; padded output
    rows are sliced off), runs the fused kernel, and rewrites never-visited
    destination groups to ``act(bias)`` — the value the unfused oracle
    assigns to an all-zero aggregation row.  ``quantized`` quantizes the
    float weights here
    (per-output-channel, identical to ``photonic.quant.quantize_weights``)
    and selects the int8 sign-split combine epilogue; see the kernel
    docstring for its documented tolerance vs the per-tensor-scale oracle.
    Returns [G_dst * V, F_out].
    """
    interpret = auto_interpret() if interpret is None else interpret
    f_in, f_out = w.shape
    v, n = blocks.shape[1:]
    featp = _pad_groups(_pad_to(feat, 1, lane), n)
    bias_row = (jnp.zeros((f_out,), feat.dtype) if bias is None
                else bias.astype(feat.dtype))
    biasp = _pad_to(bias_row.reshape(1, f_out), 1, lane)
    apply_deg = inv_deg is not None
    invd = (jnp.ones((num_dst_groups * v, 1), feat.dtype) if not apply_deg
            else inv_deg.reshape(num_dst_groups * v, 1).astype(feat.dtype))
    invd = _pad_groups(invd, v)

    w_scale = None
    if quantized:
        # Weight quantization matches the unfused oracle exactly (shared
        # scheme); zero-padded int8 rows/columns stay exact no-ops, and
        # padded output channels get scale 0 (sliced off below).
        wq, sw = quantize_weights(w, QuantConfig())
        wp = _pad_to(_pad_to(wq, 0, lane), 1, lane)
        w_scale = _pad_to(sw.reshape(1, f_out).astype(jnp.float32), 1, lane)
    else:
        wp = _pad_to(_pad_to(w, 0, lane), 1, lane)

    out = fused_block_spmm(
        _pad_tiles(blocks), block_row, block_col, featp, wp, biasp, invd,
        num_dst_groups, activation=activation, apply_deg=apply_deg,
        reduce=reduce, w_scale=w_scale, interpret=interpret,
    )
    out = _unpad_groups(out, v)[:, :f_out]
    # Destination groups with no tiles are never visited by the kernel, so
    # their output blocks are uninitialized; the oracle maps their all-zero
    # aggregation rows through the epilogue, i.e. to act(bias) (a zero row
    # quantizes to zeros, so this holds for the int8 epilogue too).
    visited = jnp.zeros((num_dst_groups,), jnp.bool_).at[block_row].set(True)
    fill = apply_epilogue_activation(bias_row.astype(jnp.float32),
                                     activation).astype(out.dtype)
    return jnp.where(jnp.repeat(visited, v)[:, None], out, fill[None, :])


@functools.partial(
    jax.jit,
    static_argnames=("num_dst_groups", "negative_slope", "lane", "interpret"),
)
def attention_block_spmm_padded(
    blocks: jax.Array,       # [B, V, N] CSR-row-sorted tiles
    block_row: jax.Array,    # [B] int32, non-decreasing
    block_col: jax.Array,    # [B] int32
    values: jax.Array,       # [G_src * N, H, F] projected values W h
    src_scores: jax.Array,   # [G_src * N, H]
    dst_scores: jax.Array,   # [G_dst * V, H]
    num_dst_groups: int,
    negative_slope: float = 0.2,
    lane: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """attention_block_spmm with the self term, lane and tile padding.

    Builds each destination row's self term from its own source-side row
    (node i is row i on both sides), pads H*F to ``lane`` multiples and
    V/N to sublane multiples, runs the kernel, slices the padding off, and
    gives rows of never-visited groups their self value: a softmax over
    the self term alone.  Returns [G_dst * V, H, F].
    """
    interpret = auto_interpret() if interpret is None else interpret
    v, n = blocks.shape[1:]
    heads, f = values.shape[1:]
    pad_dst = num_dst_groups * v
    vals2d = values.reshape(values.shape[0], heads * f)
    self_vals = to_dst_rows(vals2d, pad_dst)
    self_logits = jax.nn.leaky_relu(
        to_dst_rows(src_scores, pad_dst) + dst_scores, negative_slope)
    # Source scores per source group, heads on the sublanes: [G_src, H, N8].
    ssrc = _pad_groups(src_scores, n).reshape(
        -1, n + (-n) % SUBLANE, heads).transpose(0, 2, 1)
    out = attention_block_spmm(
        _pad_tiles(blocks), block_row, block_col,
        _pad_groups(_pad_to(vals2d, 1, lane), n), ssrc,
        _pad_groups(dst_scores, v), _pad_groups(self_logits, v),
        _pad_groups(_pad_to(self_vals, 1, lane), v),
        num_dst_groups, heads, f, v, n, negative_slope=negative_slope,
        interpret=interpret)
    out = _unpad_groups(out, v)[:, :heads * f]
    visited = jnp.zeros((num_dst_groups,), jnp.bool_).at[block_row].set(True)
    out = jnp.where(jnp.repeat(visited, v)[:, None], out, self_vals)
    return out.reshape(pad_dst, heads, f)


def aggregate_blocked_kernel(pg_or_bg, feat_padded: jax.Array,
                             block_f: int = 128,
                             interpret: bool | None = None) -> jax.Array:
    """GHOST blocked aggregation via the Pallas kernel.

    Accepts a PartitionedGraph (numpy) or BlockedGraph (device) container.
    """
    if isinstance(pg_or_bg, PartitionedGraph):
        blocks = jnp.asarray(pg_or_bg.blocks)
        row = jnp.asarray(pg_or_bg.block_row)
        col = jnp.asarray(pg_or_bg.block_col)
        g_dst = pg_or_bg.num_dst_groups
    else:
        blocks, row, col = pg_or_bg.blocks, pg_or_bg.block_row, pg_or_bg.block_col
        g_dst = pg_or_bg.num_dst_groups
    return block_spmm_padded(blocks, row, col, feat_padded, g_dst,
                             block_f=block_f, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret"),
)
def quantized_matmul_kernel(
    x: jax.Array,          # [M, K] float
    w: jax.Array,          # [K, N] float
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Quantize x (per-tensor) and w (per-channel), multiply on the int8
    kernel, dequantize.  Matches photonic.quant.quantized_matmul numerics."""
    interpret = auto_interpret() if interpret is None else interpret
    cfg = QuantConfig()
    sx = compute_scale(x, axis=None, qmax=cfg.qmax)
    xq = quantize(x, sx, cfg.qmax)
    wq, sw = quantize_weights(w, cfg)

    m, k = xq.shape
    n = wq.shape[1]
    bm, bn, bk = (min(block_m, m), min(block_n, n), min(block_k, k))
    xq = _pad_to(_pad_to(xq, 0, bm), 1, bk)
    wq = _pad_to(_pad_to(wq, 0, bk), 1, bn)
    swp = _pad_to(sw.reshape(-1), 0, bn)

    out = quant_matmul(
        xq, wq,
        jnp.asarray([sx], jnp.float32).reshape(1),
        swp.astype(jnp.float32),
        block_m=bm, block_n=bn, block_k=bk,
        out_dtype=jnp.float32,
        interpret=interpret,
    )
    return out[:m, :n].astype(w.dtype)
