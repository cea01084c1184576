"""Pallas TPU kernel: fused blocked-sparse aggregation + combine epilogue.

GHOST runs aggregate and combine as separate pipeline stages; the blocked
TPU port in ``block_spmm.py`` mirrors that literally and therefore writes
the aggregated intermediate ``[G_dst*V, F_in]`` to HBM before the dense
combine matmul reads it straight back.  This kernel fuses the combine into
the SpMM epilogue (the standard GNN-accelerator fusion, cf. Zhang et al.
arXiv 2306.14052 / VersaGNN arXiv 2105.01280): the per-row aggregation
accumulator never leaves VMEM, and on the *last* visit to an output row it
is multiplied by the resident weight tile (plus bias and an optional
activation) before the only HBM write — ``[V, F_out]`` instead of
``[V, F_in]`` + a later round-trip.

Dataflow (extends the scalar-prefetch/CSR-sorted ``block_spmm`` design):

* ``block_row`` / ``block_col`` are scalar-prefetched into SMEM; the
  BlockSpec index maps steer the HBM->VMEM DMAs so all-zero adjacency
  tiles are never fetched (GHOST's zero-block skipping).
* Tiles must be CSR-sorted by destination row (``partition_graph``'s
  default fetch order).  Consecutive grid steps that share a destination
  row accumulate into a VMEM *scratch* buffer ``acc[V, F_in]``; the buffer
  is initialized on the first visit to each row (``@pl.when``) and consumed
  by the combine epilogue on the last.
* The weight tile ``[F_in, F_out]`` and bias row use constant index maps,
  so Pallas keeps them VMEM-resident across the whole grid — they are
  DMA'd once, exactly like weights in the canonical fused-matmul pattern.
* MEAN reduction folds in as a per-row scale of the accumulator by the
  precomputed inverse degree (graph-static; see
  ``core.aggregate.blocked_degrees``) *before* the combine matmul, which
  matches the unfused oracle's normalize-then-combine order.

Reduce modes:

* ``"sum"`` (also carrying MEAN via ``apply_deg``): the accumulator is
  zero-initialized and each visit adds one dense tile product.
* ``"max"``: the paper's optical-comparator reduce.  The accumulator is
  initialized to ``-inf`` and each visit merges the masked per-tile
  feature maximum with ``jnp.maximum``; the epilogue rewrites rows that
  never saw an edge (still ``-inf``) to 0, exactly like the comparator
  oracle (no inputs -> no output), before running the same combine.
  Edge multiplicity is irrelevant for MAX, so only the ``blocks != 0``
  mask enters.

Combine epilogues:

* float (default): ``out[r] = act((acc[r] * inv_deg[r]) @ W + bias)``.
* ``quantized`` — the photonic 8-bit sign-split MVM (paper Section 3.3.2),
  reusing ``kernels/quant_matmul.py``'s accumulate-dequantize scheme: the
  weight tile arrives pre-quantized int8 with per-output-channel scales,
  the row-block accumulator is quantized *in the epilogue* with a
  per-row-block symmetric scale (``max|acc| / 127`` over the ``[V, F_in]``
  block — each destination row block is one MR-bank mapping, so the
  amplitude normalization is per mapping), the product accumulates in
  int32 (the photodetector current sum), and dequantization is the
  balanced-photodetector rescale ``s_act * s_w``.

  Numerics contract: the unfused oracle
  (``photonic.quant.quantized_matmul``) uses one *per-tensor* activation
  scale over the whole aggregated matrix, which cannot be known before
  every row finishes aggregating — materializing it is exactly the HBM
  round-trip this kernel exists to remove.  The fused path's per-row-block
  scales are a finer granularity of the same symmetric scheme, so outputs
  agree with the oracle within the int8 quantization error of *both*
  paths:  |fused - unfused|[i, j] <= 0.5 * (s_blk(i) + s_tensor) *
  sum_k |W_deq[k, j]|  (the documented int8 tolerance; both paths share
  identical weight quantization, so only the activation rounding differs).
  tests/test_properties.py checks this bound property-style.

Grid: (num_blocks,).  VMEM working set per step:
  adjacency tile   V x N
  feature tile     N x F_in   (full feature width; the combine epilogue
                               needs the complete row accumulator, so the
                               feature dim is not grid-tiled — when F_in is
                               large the order planner in core.aggregate
                               prefers combine-first and this kernel runs
                               over the narrower F_out instead)
  weight tile      F_in x F_out   (resident; int8 when quantized)
  accumulator      V x F_in       (scratch, fp32)
  output tile      V x F_out

Destination groups with no tiles are never visited; the wrapper in
``kernels.ops`` patches them to ``act(bias)`` — exactly what the unfused
oracle produces for an all-zero aggregation row (in both float and
quantized epilogues: a zero row quantizes to zeros).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Shared vocabulary with the XLA-side _apply_activation (single source of
# truth, so the fused and unfused paths can never drift apart in what they
# accept).  This import cannot cycle: core.aggregate only reaches back into
# kernels lazily, inside functions.
from repro.core.aggregate import EPILOGUE_ACTIVATIONS

FUSED_REDUCES = ("sum", "max")


def apply_epilogue_activation(y: jax.Array, activation: str) -> jax.Array:
    """In-kernel (Pallas-safe) twin of core.aggregate._apply_activation."""
    if activation == "relu":
        return jnp.maximum(y, 0.0)
    if activation == "elu":
        return jnp.where(y > 0.0, y, jnp.expm1(y))
    return y


def _kernel(block_row, block_col, blocks_ref, feat_ref, w_ref, bias_ref,
            invdeg_ref, *refs, num_blocks: int, activation: str,
            apply_deg: bool, reduce: str, quantized: bool):
    if quantized:
        sw_ref, out_ref, acc_ref = refs
    else:
        sw_ref = None
        out_ref, acc_ref = refs
    b = pl.program_id(0)

    first_visit = jnp.logical_or(
        b == 0, block_row[jnp.maximum(b, 1) - 1] != block_row[b]
    )
    # CSR row-sorted tiles: the final grid step is always the last visit to
    # its (maximal) destination row, so clamping the lookahead is safe.
    last_visit = jnp.logical_or(
        b == num_blocks - 1,
        block_row[jnp.minimum(b + 1, num_blocks - 1)] != block_row[b],
    )

    if reduce == "max":
        @pl.when(first_visit)
        def _init():
            acc_ref[...] = jnp.full_like(acc_ref, -jnp.inf)

        # Optical-comparator merge: a running maximum over the tile's
        # source columns, one [V, F] masked candidate per column (Mosaic
        # lowers these 2-D selects; it refuses the [V, N, 1] mask a 3-D
        # candidate needs).  Multiplicity does not enter MAX, only edge
        # presence.
        tile = blocks_ref[...]                                     # [V, N]
        feat = feat_ref[...].astype(jnp.float32)                   # [N, F]
        acc = acc_ref[...]
        for j in range(tile.shape[1]):
            acc = jnp.maximum(acc, jnp.where(tile[:, j:j + 1] != 0,
                                             feat[j:j + 1, :], -jnp.inf))
        acc_ref[...] = acc
    else:
        @pl.when(first_visit)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(
            blocks_ref[...],
            feat_ref[...].astype(blocks_ref.dtype),
            preferred_element_type=jnp.float32,
        ).astype(acc_ref.dtype)

    @pl.when(last_visit)
    def _combine():
        acc = acc_ref[...]
        if reduce == "max":
            # Rows with tiles but no in-tile edges never merged a finite
            # candidate; the comparator oracle maps them to 0.
            acc = jnp.where(jnp.isfinite(acc), acc, 0.0)
        if apply_deg:  # MEAN: normalize before combine, like the oracle
            acc = acc * invdeg_ref[...]
        if quantized:
            # Photonic sign-split MVM: symmetric int8 quantization of the
            # row-block accumulator (per-mapping amplitude scale), int32
            # accumulation, BPD recombination + transimpedance rescale.
            s_act = jnp.maximum(jnp.max(jnp.abs(acc)), 1e-12) / 127.0
            q = jnp.clip(jnp.round(acc / s_act), -127.0, 127.0
                         ).astype(jnp.int8)
            prod = jax.lax.dot_general(
                q, w_ref[...],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            y = prod.astype(jnp.float32) * s_act * sw_ref[...]
        else:
            y = jnp.dot(acc, w_ref[...].astype(acc.dtype),
                        preferred_element_type=jnp.float32)
        y = y + bias_ref[...].astype(y.dtype)
        out_ref[...] = apply_epilogue_activation(y, activation).astype(
            out_ref.dtype)


def fused_block_spmm(
    blocks: jax.Array,      # [B, V, N] tile values (CSR-sorted by row)
    block_row: jax.Array,   # [B] int32 destination-group ids (non-decreasing)
    block_col: jax.Array,   # [B] int32 source-group ids
    feat: jax.Array,        # [G_src * N, F_in] padded source features
    w: jax.Array,           # [F_in, F_out] combine weights (int8 if quantized)
    bias: jax.Array,        # [1, F_out] combine bias (zeros when unused)
    inv_deg: jax.Array,     # [G_dst * V, 1] inverse degrees (ones for SUM)
    num_dst_groups: int,
    activation: str = "none",
    apply_deg: bool = False,
    reduce: str = "sum",
    w_scale: jax.Array | None = None,  # [1, F_out] dequant scales (quantized)
    interpret: bool = False,
) -> jax.Array:
    """Fused out[r*V:(r+1)*V] = act(epilogue(reduce_b blocks[b] @ feat_tile)).

    Returns [num_dst_groups * V, F_out].  Feature/weight dims must already
    be lane-padded (see ops.fused_block_spmm_padded for the padding and the
    unvisited-row patch-up).  ``w_scale`` present selects the int8
    quantized combine epilogue; ``w`` must then be the int8 weight tile.
    """
    num_blocks, v, n = blocks.shape
    f_in = feat.shape[1]
    f_out = w.shape[1]
    quantized = w_scale is not None
    if w.shape[0] != f_in:
        raise ValueError(f"weight rows {w.shape[0]} != feature dim {f_in}")
    if feat.shape[0] % n:
        raise ValueError("feat rows must be a multiple of the tile width N")
    if activation not in EPILOGUE_ACTIVATIONS:
        raise ValueError(f"unknown epilogue activation '{activation}'; "
                         f"expected one of {EPILOGUE_ACTIVATIONS}")
    if reduce not in FUSED_REDUCES:
        raise ValueError(f"unknown fused reduce '{reduce}'; "
                         f"expected one of {FUSED_REDUCES}")
    if reduce == "max" and apply_deg:
        raise ValueError("MAX reduce has no degree normalization")
    if quantized and w.dtype != jnp.int8:
        raise ValueError("quantized epilogue expects int8 weights "
                         f"(got {w.dtype}); quantize at the call site")

    # Roofline accounting for the scheduler: one SpMM visit per tile plus
    # one combine matmul per destination row (num_dst_groups upper bound).
    w_bytes = 1 if quantized else 4
    cost = pl.CostEstimate(
        flops=2 * num_blocks * v * n * f_in
        + 2 * num_dst_groups * v * f_in * f_out,
        bytes_accessed=(4 * num_blocks * (v * n + n * f_in)
                        + w_bytes * f_in * f_out
                        + 4 * num_dst_groups * v * f_out),
        transcendentals=0,
    )

    kernel = functools.partial(_kernel, num_blocks=num_blocks,
                               activation=activation, apply_deg=apply_deg,
                               reduce=reduce, quantized=quantized)
    in_specs = [
        pl.BlockSpec((None, v, n), lambda b, br, bc: (b, 0, 0)),
        pl.BlockSpec((n, f_in), lambda b, br, bc: (bc[b], 0)),
        pl.BlockSpec((f_in, f_out), lambda b, br, bc: (0, 0)),
        pl.BlockSpec((1, f_out), lambda b, br, bc: (0, 0)),
        pl.BlockSpec((v, 1), lambda b, br, bc: (br[b], 0)),
    ]
    operands = [feat, w, bias, inv_deg]
    if quantized:
        in_specs.append(pl.BlockSpec((1, f_out), lambda b, br, bc: (0, 0)))
        operands.append(w_scale)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(num_blocks,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (v, f_out), lambda b, br, bc: (br[b], 0)
            ),
            scratch_shapes=[pltpu.VMEM((v, f_in), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((num_dst_groups * v, f_out),
                                       feat.dtype),
        cost_estimate=cost,
        interpret=interpret,
    )(block_row, block_col, blocks, *operands)
    return out
