"""Aggregate phase — the paper's reduce-unit dataflow, in JAX.

Two numerically-equivalent backends:

* ``aggregate_edges``   — edge-list reference (segment ops).  Used for
  training and as the oracle in tests.
* ``aggregate_blocked`` — the GHOST V x N blocked dataflow (Section 3.3.1 +
  3.4.1): only non-zero adjacency tiles are touched; each tile contributes a
  dense (V x N) @ (N x F) product — exactly what the coherent-summation MR
  array computes per mapping, and exactly what the MXU wants.  The Pallas
  kernel in ``repro.kernels.block_spmm`` implements the same contraction with
  explicit VMEM tiling; this jnp version is its oracle and the CPU fallback.

On top of the plain aggregation, ``aggregate_combine_blocked`` runs a whole
aggregate+combine stage pair (the GReTA reduce->transform step) through a
static **order planner**: it picks aggregate-first vs combine-first from the
tile FLOP counts (combine-first shrinks the SpMM width whenever
``F_out < F_in`` — GHOST's own transform-first GAT ordering, applied
cost-wise to every layer), and on the ``pallas_fused`` backend it lowers the
aggregate-first order to the fused SpMM+combine epilogue kernel in
``repro.kernels.fused_block_spmm`` so the aggregated intermediate never
round-trips through HBM.

Reduce ops: SUM / MEAN / MAX, matching the paper's reduce-unit modes (plain
coherent summation, the trailing 1/n MR, and the optical comparator).
MEAN degrees are graph-static: ``to_blocked`` precomputes them once per
graph (``BlockedGraph.deg``) so no forward pass re-reduces the tiles.
"""

from __future__ import annotations

import contextlib
import enum
import threading
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.partition import PartitionedGraph


class ReduceOp(str, enum.Enum):
    SUM = "sum"
    MEAN = "mean"
    MAX = "max"


# ---------------------------------------------------------------------------
# Backend selection: "jnp" (einsum/segment ops, the oracle), "pallas" (the
# unfused block_spmm kernel; interpret mode on CPU), or "pallas_fused"
# (block_spmm for plain aggregations + the fused aggregate+combine kernel
# inside aggregate_combine_blocked).  The serving engine flips this
# per-executor; layers and models stay backend-agnostic.  The stack is
# thread-local so a threaded intake path can never race one executor's
# selection against another's.
# ---------------------------------------------------------------------------

_BACKEND_TLS = threading.local()
AGGREGATE_BACKENDS = ("jnp", "pallas", "pallas_fused")
_PALLAS_BACKENDS = ("pallas", "pallas_fused")


def _backend_stack() -> list:
    stack = getattr(_BACKEND_TLS, "stack", None)
    if stack is None:
        stack = _BACKEND_TLS.stack = ["jnp"]
    return stack


def active_aggregate_backend() -> str:
    return _backend_stack()[-1]


@contextlib.contextmanager
def aggregate_backend(name: str):
    """Route blocked SUM/MEAN aggregation and GAT attention through the
    chosen backend.

    The selection is read at trace time, so wrapping a jit'd call site routes
    every blocked aggregation inside that trace.  MAX always uses the jnp
    path (the Pallas kernel is an SpMM; the optical comparator has no MXU
    analogue).  Selections are per-thread: pushing a backend in one thread
    is invisible to every other thread.
    """
    if name not in AGGREGATE_BACKENDS:
        raise ValueError(f"unknown aggregate backend '{name}'; "
                         f"expected one of {AGGREGATE_BACKENDS}")
    stack = _backend_stack()
    stack.append(name)
    try:
        yield
    finally:
        stack.pop()


# ---------------------------------------------------------------------------
# Kernel-config resolution: an optional thread-local hook that lets a tuner
# (repro.kernels.autotune) or an explicit test override steer *how* each
# aggregate+combine site lowers — fused vs unfused, execution order, and the
# kernel tile widths — without the layers knowing anything about it.  Like
# the backend selection above, the resolver is consulted at trace time, so
# wrapping a jit'd call site bakes the chosen configs into that trace.
# ---------------------------------------------------------------------------


class KernelSite(NamedTuple):
    """Static (trace-time) description of one aggregate+combine call site.

    Everything here is a Python constant at trace time — tile geometry,
    feature widths, reduce mode, dtype, quantization, and the active
    backend — i.e. exactly the inputs a shape-class autotuner keys on.
    """

    num_blocks: int
    num_dst_groups: int
    num_src_groups: int
    v: int
    n: int
    f_in: int
    f_out: int
    reduce: str
    dtype: str
    quantized: bool
    backend: str


_RESOLVER_TLS = threading.local()


def _resolver_stack() -> list:
    stack = getattr(_RESOLVER_TLS, "stack", None)
    if stack is None:
        stack = _RESOLVER_TLS.stack = [None]
    return stack


def active_kernel_resolver():
    return _resolver_stack()[-1]


@contextlib.contextmanager
def kernel_config_scope(resolver):
    """Install a kernel-config resolver for aggregate_combine_blocked.

    ``resolver(site: KernelSite)`` returns a config object or None (None =
    keep the defaults).  The config is duck-typed; the attributes read are

      * ``fused``   — Optional[bool]: force the fused epilogue kernel on or
        off (honored only on the ``pallas_fused`` backend, where fusion is
        the default; ``pallas``/``jnp`` keep their meaning).
      * ``order``   — Optional[str]: combination order, consulted only when
        the call site asked for ``"auto"`` (explicit order and the
        nonlinear-stage pinning always win).
      * ``block_f`` — Optional[int]: feature tile width of the unfused
        SpMM kernel.
      * ``lane``    — Optional[int]: lane padding of the fused kernel.
      * ``shard``   — Optional[str]: consulted only under an active
        ``shard_scope``; ``"none"`` pins the site to the single-device
        lowering, anything else keeps the scope's strategy.

    Scopes nest and are per-thread, mirroring ``aggregate_backend``.
    """
    stack = _resolver_stack()
    stack.append(resolver)
    try:
        yield
    finally:
        stack.pop()


# ---------------------------------------------------------------------------
# Shard scope: an optional thread-local (mesh, axis) selection that routes
# aggregate_combine_blocked through the multi-device feature-dim partition
# (see the "Sharded execution" section below).  Like the backend stack and
# the kernel-config resolver, the scope is consulted at trace time and is
# per-thread, so one executor's mesh never leaks into another thread's
# traces.
# ---------------------------------------------------------------------------


class ShardContext(NamedTuple):
    """Active mesh selection for sharded aggregate+combine lowering."""

    mesh: object        # jax.sharding.Mesh
    axis: str           # 1-D partition axis name (conventionally "data")

    @property
    def num_shards(self) -> int:
        return int(self.mesh.shape[self.axis])


_SHARD_TLS = threading.local()


def _shard_stack() -> list:
    stack = getattr(_SHARD_TLS, "stack", None)
    if stack is None:
        stack = _SHARD_TLS.stack = [None]
    return stack


def active_shard_context() -> Optional[ShardContext]:
    return _shard_stack()[-1]


@contextlib.contextmanager
def shard_scope(mesh, axis: str = "data"):
    """Route blocked aggregate+combine stages across a device mesh.

    Inside the scope, ``aggregate_combine_blocked`` lowers SUM/MEAN/MAX
    non-quantized stages through the feature-dim partition
    (``aggregate_combine_sharded``'s "feature" strategy): each device owns
    an F_in slice of the SpMM and the matching combine-weight rows, and one
    ``psum`` over the contracted dimension rebuilds the output.  This is
    the strategy that needs no host-side graph resharding, so it drops into
    existing jit traces (including vmapped serving executors) untouched.

    ``mesh=None`` suppresses any enclosing scope — the sharded kernels use
    it so their per-device bodies never recurse into the router.  Scopes
    nest and are per-thread, mirroring ``aggregate_backend``.
    """
    ctx = None
    if mesh is not None:
        if axis not in mesh.axis_names:
            raise ValueError(f"mesh has no axis '{axis}'; "
                             f"axes are {tuple(mesh.axis_names)}")
        ctx = ShardContext(mesh=mesh, axis=axis)
    stack = _shard_stack()
    stack.append(ctx)
    try:
        yield
    finally:
        stack.pop()


class BlockedGraph(NamedTuple):
    """Device-resident view of a PartitionedGraph (static shapes).

    ``deg`` holds the per-destination-row MEAN-reduce degrees.  Degree is
    graph-static, so it is computed once (``to_blocked`` for host graphs;
    ``with_degrees`` inside a serving trace) and reused by every layer and
    backend instead of being re-reduced from the tiles on each forward.
    """

    blocks: jax.Array      # [B, V, N]
    block_row: jax.Array   # [B]
    block_col: jax.Array   # [B]
    num_dst_groups: int
    num_src_groups: int
    v: int
    n: int
    num_nodes: int
    deg: Optional[jax.Array] = None  # [G_dst * V] or None (derive on demand)


def to_blocked(pg: PartitionedGraph) -> BlockedGraph:
    # Degree = sum of tile entries: multiplicities of duplicate edges were
    # accumulated into the tile values at partition time, so this matches
    # the edge-list backend's per-edge count exactly.  Hoisted here (once
    # per graph) because it is structure-only data.
    deg = np.zeros((pg.num_dst_groups, pg.v), np.float32)
    np.add.at(deg, pg.block_row, pg.blocks.sum(axis=2, dtype=np.float32))
    return BlockedGraph(
        blocks=jnp.asarray(pg.blocks),
        block_row=jnp.asarray(pg.block_row),
        block_col=jnp.asarray(pg.block_col),
        num_dst_groups=pg.num_dst_groups,
        num_src_groups=pg.num_src_groups,
        v=pg.v,
        n=pg.n,
        num_nodes=pg.num_nodes,
        deg=jnp.asarray(deg.reshape(-1)),
    )


def blocked_degrees(bg: BlockedGraph) -> jax.Array:
    """Per-destination-row degrees [G_dst * V] (precomputed or derived)."""
    if bg.deg is not None:
        return bg.deg
    deg_partial = bg.blocks.sum(axis=2)                        # [B, V]
    deg = jax.ops.segment_sum(deg_partial, bg.block_row,
                              num_segments=bg.num_dst_groups)
    return deg.reshape(bg.num_dst_groups * bg.v)


def with_degrees(bg: BlockedGraph) -> BlockedGraph:
    """Attach the degree vector so downstream layers share one reduction.

    Used by serving executors whose BlockedGraphs are built from batched
    device arrays (no host PartitionedGraph to hoist from): calling this
    once at trace entry makes every MEAN layer in the model reuse a single
    segment-sum instead of re-deriving degrees per layer.
    """
    if bg.deg is not None:
        return bg
    return bg._replace(deg=blocked_degrees(bg))


# ---------------------------------------------------------------------------
# Edge-list reference backend.
# ---------------------------------------------------------------------------

def aggregate_edges(
    edge_src: jax.Array,
    edge_dst: jax.Array,
    feat: jax.Array,
    num_nodes: int,
    reduce: ReduceOp = ReduceOp.SUM,
    edge_weights: jax.Array | None = None,
) -> jax.Array:
    """Edge-list aggregation: out[v] = reduce_{(u,v) in E} w_uv * feat[u]."""
    msgs = feat[edge_src]
    if edge_weights is not None:
        msgs = msgs * edge_weights[:, None]
    if reduce in (ReduceOp.SUM, ReduceOp.MEAN):
        out = jax.ops.segment_sum(msgs, edge_dst, num_segments=num_nodes)
        if reduce == ReduceOp.MEAN:
            deg = jax.ops.segment_sum(
                jnp.ones_like(edge_dst, feat.dtype), edge_dst, num_segments=num_nodes
            )
            out = out / jnp.maximum(deg, 1.0)[:, None]
        return out
    if reduce == ReduceOp.MAX:
        out = jax.ops.segment_max(msgs, edge_dst, num_segments=num_nodes)
        # Isolated vertices get -inf from segment_max; zero them like the
        # hardware comparator (no inputs -> no output).
        return jnp.where(jnp.isfinite(out), out, 0.0)
    raise ValueError(f"unknown reduce {reduce}")


# ---------------------------------------------------------------------------
# Blocked (GHOST) backend.
# ---------------------------------------------------------------------------

def aggregate_blocked(
    bg: BlockedGraph,
    feat_padded: jax.Array,
    reduce: ReduceOp = ReduceOp.SUM,
    block_f: Optional[int] = None,
) -> jax.Array:
    """Blocked aggregation over non-zero tiles only.

    The Pallas backends run SUM and MEAN on the ``block_spmm`` kernel.  MAX
    has no unfused kernel: on every backend it is computed by the jnp
    comparator below (the fused kernel's comparator mode is reached only
    through ``aggregate_combine_blocked`` on ``pallas_fused``).

    Args:
      bg: blocked adjacency (non-zero tiles).
      feat_padded: [G_src * N, F] source features, padded (see
        PartitionedGraph.pad_features).
      reduce: SUM / MEAN / MAX.
      block_f: feature tile width of the Pallas SpMM kernel (autotuner
        knob; None = the kernel's 128-lane default, ignored on jnp).

    Returns:
      [G_dst * V, F] aggregated features (padded rows included).
    """
    f = feat_padded.shape[-1]

    def mean_normalize(out):
        # Shared by all backends — their MEAN semantics must never drift
        # apart.  Degrees come precomputed with the graph when available
        # (structure-static; see BlockedGraph.deg).  Normalization is an
        # explicit reciprocal-multiply, NOT a divide: when deg is a trace
        # constant XLA rewrites x/deg into x*(1/deg) anyway, so writing the
        # multiply keeps constant-deg and traced-deg programs (serving
        # reference vs executor) bit-identical, and matches the fused
        # kernel's epilogue exactly.
        deg = blocked_degrees(bg).astype(out.dtype)
        inv = 1.0 / jnp.maximum(deg, 1.0)
        return out * inv[:, None]

    if active_aggregate_backend() in _PALLAS_BACKENDS and reduce in (
            ReduceOp.SUM, ReduceOp.MEAN):
        # Lazy import: kernels.ops imports core.partition; importing it at
        # module scope would cycle through core/__init__.
        from repro.kernels.ops import block_spmm_padded

        out = block_spmm_padded(bg.blocks, bg.block_row, bg.block_col,
                                feat_padded, bg.num_dst_groups,
                                block_f=block_f or 128)
        if reduce == ReduceOp.MEAN:
            out = mean_normalize(out)
        return out.astype(feat_padded.dtype)

    src_tiles = feat_padded.reshape(bg.num_src_groups, bg.n, f)[bg.block_col]  # [B,N,F]

    if reduce in (ReduceOp.SUM, ReduceOp.MEAN):
        partial = jnp.einsum(
            "bvn,bnf->bvf", bg.blocks, src_tiles.astype(bg.blocks.dtype)
        )
        out = jax.ops.segment_sum(partial, bg.block_row, num_segments=bg.num_dst_groups)
        out = out.reshape(bg.num_dst_groups * bg.v, f)
        if reduce == ReduceOp.MEAN:
            out = mean_normalize(out)
        return out.astype(feat_padded.dtype)

    if reduce == ReduceOp.MAX:
        mask = (bg.blocks != 0)[..., None]                          # [B,V,N,1]
        neg = jnp.asarray(-jnp.inf, feat_padded.dtype)
        cand = jnp.where(mask, src_tiles[:, None, :, :], neg)       # [B,V,N,F]
        partial = cand.max(axis=2)                                  # [B,V,F]
        out = jax.ops.segment_max(partial, bg.block_row, num_segments=bg.num_dst_groups)
        out = out.reshape(bg.num_dst_groups * bg.v, f)
        return jnp.where(jnp.isfinite(out), out, 0.0)

    raise ValueError(f"unknown reduce {reduce}")


# ---------------------------------------------------------------------------
# Fused aggregate+combine with combination-order planning.
#
# The GReTA reduce->transform pair admits two execution orders (paper
# Section 3.4.2 applies it to GAT; the FLOP argument applies everywhere):
#
#   aggregate_first:  (A X) W    SpMM over F_in,  dense combine to F_out
#   combine_first:    A (X W)    dense combine to F_out, SpMM over F_out
#
# Linearity makes them mathematically identical for SUM, and for MEAN too
# (D^-1 A (X W) == (D^-1 A X) W — the degree scale is per-row).  The planner
# picks the cheaper order from static tile counts; the serving engine's
# "pallas_fused" backend additionally lowers the aggregate-first order onto
# the fused epilogue kernel so the [G_dst*V, F_in] intermediate never
# touches HBM.
# ---------------------------------------------------------------------------

COMBINE_ORDERS = ("auto", "aggregate_first", "combine_first")


class CombinePlan(NamedTuple):
    """Static cost breakdown behind one order decision (roofline inputs)."""

    order: str                     # "aggregate_first" | "combine_first"
    flops_aggregate_first: int     # 2*B*V*N*F_in + 2*G_dst*V*F_in*F_out
    flops_combine_first: int       # 2*G_src*N*F_in*F_out + 2*B*V*N*F_out
    fused_hbm_bytes_saved: int     # the [G_dst*V, F_in] fp32 write+read the
                                   # fused epilogue eliminates (agg-first)

    def to_dict(self) -> dict:
        return dict(self._asdict())


# Process-wide, not per thread: the always-on serve loop traces executors
# in its worker threads, and a report reads the log from another thread.
_PLAN_LOCK = threading.Lock()
_PLAN_LOG: dict = {}


def planner_decisions() -> list:
    """Order decisions recorded at trace time, one dict per distinct
    (tile geometry, F_in, F_out, reduce, backend) site — benchmark/report
    fodder, deduplicated so jit retraces don't grow it."""
    with _PLAN_LOCK:
        items = list(_PLAN_LOG.items())
    return [
        {"blocks": k[0], "v": k[1], "n": k[2], "g_dst": k[3], "g_src": k[4],
         "f_in": k[5], "f_out": k[6], "reduce": k[7], "backend": k[8],
         "quantized": k[9], **plan.to_dict()}
        for k, plan in items
    ]


def clear_planner_log() -> None:
    with _PLAN_LOCK:
        _PLAN_LOG.clear()


def _order_flops(b: int, v: int, n: int, g_dst: int, g_src: int,
                 f_in: int, f_out: int) -> tuple[int, int]:
    """(aggregate_first, combine_first) FLOP totals for one stage pair."""
    agg_first = 2 * b * v * n * f_in + 2 * g_dst * v * f_in * f_out
    comb_first = 2 * g_src * n * f_in * f_out + 2 * b * v * n * f_out
    return agg_first, comb_first


def _plan_order_from_geom(b: int, v: int, n: int, g_dst: int, g_src: int,
                          f_in: int, f_out: int) -> str:
    """The auto order decision from raw geometry — used by the sharded
    forward, which must plan on GLOBAL tile counts (a per-shard plan could
    flip the choice and break bit-exactness vs the single-device run)."""
    agg_first, comb_first = _order_flops(b, v, n, g_dst, g_src, f_in, f_out)
    return "aggregate_first" if agg_first <= comb_first else "combine_first"


def plan_combine_order(bg: BlockedGraph, f_in: int, f_out: int,
                       order: str = "auto") -> CombinePlan:
    """Choose the aggregate/combine execution order from static FLOPs.

    All inputs are trace-time constants (tile counts and feature widths),
    so the decision is static per jit trace — no data-dependent control
    flow enters the compiled program.  ``order`` overrides the choice.
    """
    if order not in COMBINE_ORDERS:
        raise ValueError(f"unknown combine order '{order}'; "
                         f"expected one of {COMBINE_ORDERS}")
    b = int(bg.blocks.shape[0])
    agg_first, comb_first = _order_flops(
        b, bg.v, bg.n, bg.num_dst_groups, bg.num_src_groups, f_in, f_out)
    if order == "auto":
        order = "aggregate_first" if agg_first <= comb_first else "combine_first"
    return CombinePlan(
        order=order,
        flops_aggregate_first=agg_first,
        flops_combine_first=comb_first,
        fused_hbm_bytes_saved=2 * bg.num_dst_groups * bg.v * f_in * 4,
    )


def _record_plan(bg: BlockedGraph, f_in: int, f_out: int, reduce: ReduceOp,
                 backend: str, plan: CombinePlan,
                 quantized: bool = False) -> None:
    key = (int(bg.blocks.shape[0]), bg.v, bg.n, bg.num_dst_groups,
           bg.num_src_groups, f_in, f_out, str(reduce.value), backend,
           bool(quantized))
    with _PLAN_LOCK:
        _PLAN_LOG[key] = plan


# The one epilogue-activation vocabulary, shared with the fused kernel
# (repro.kernels.fused_block_spmm imports this table): every name here must
# be implemented identically by _apply_activation below (XLA path) and by
# apply_epilogue_activation in the kernel (in-kernel path), so backend
# choice can never change the supported or computed activation set.
EPILOGUE_ACTIVATIONS = ("none", "relu", "elu")


def _apply_activation(y: jax.Array, activation: Optional[str]) -> jax.Array:
    if activation in (None, "none"):
        return y
    if activation == "relu":
        return jax.nn.relu(y)
    if activation == "elu":
        return jax.nn.elu(y)
    raise ValueError(f"unknown activation '{activation}'; "
                     f"expected one of {EPILOGUE_ACTIVATIONS}")


def dense_combine(x: jax.Array, w: jax.Array, bias: Optional[jax.Array] = None,
                  activation: Optional[str] = None,
                  quantized: bool = False) -> jax.Array:
    """The combine-block transform: act(x @ w + bias).

    The one combine implementation every layer path shares — the fused
    kernel's epilogue, the combine-first projection (including GAT's
    transform-first W h), and the unfused fallback all reduce to this map.
    ``quantized`` routes through the photonic 8-bit sign-split MVM.
    """
    if quantized:
        from repro.photonic.quant import QuantConfig, quantized_matmul

        y = quantized_matmul(x, w, QuantConfig())
    else:
        y = x @ w
    if bias is not None:
        y = y + bias
    return _apply_activation(y, activation)


def aggregate_combine_blocked(
    bg: BlockedGraph,
    feat_padded: jax.Array,        # [G_src * N, F_in]
    w: jax.Array,                  # [F_in, F_out]
    bias: Optional[jax.Array] = None,
    reduce: ReduceOp = ReduceOp.SUM,
    activation: Optional[str] = None,
    order: str = "auto",
    quantized: bool = False,
) -> jax.Array:
    """One aggregate+combine stage pair with order planning and fusion.

    Computes ``act(reduce_agg(bg, feat) @ w + bias)`` — the body of every
    aggregate-first GNN layer — choosing the execution order statically
    (see ``plan_combine_order``) and, on the ``pallas_fused`` backend,
    running the aggregate-first order through the fused Pallas kernel.
    An installed kernel-config resolver (``kernel_config_scope``; the
    autotuner's hook) can additionally steer fused-vs-unfused, the auto
    order decision, and the kernel tile widths per shape class.

    Nonlinear stages pin the execution order to aggregate-first (the
    combine cannot be hoisted through them) but no longer force the slow
    path:
      * MAX reduce — lowered onto the fused kernel's comparator mode on
        ``pallas_fused`` (jnp comparator + dense combine elsewhere).
      * ``quantized`` — the int8 sign-split MVM runs as the fused kernel's
        quantized epilogue on ``pallas_fused`` (per-row-block activation
        scales; see the kernel's documented int8 tolerance vs the
        per-tensor oracle), and as the unfused
        ``photonic.quant.quantized_matmul`` elsewhere.

    Returns [G_dst * V, F_out].
    """
    f_in = int(feat_padded.shape[-1])
    f_out = int(w.shape[-1])
    backend = active_aggregate_backend()

    cfg = None
    resolver = active_kernel_resolver()
    if resolver is not None:
        cfg = resolver(KernelSite(
            num_blocks=int(bg.blocks.shape[0]),
            num_dst_groups=bg.num_dst_groups,
            num_src_groups=bg.num_src_groups,
            v=bg.v, n=bg.n, f_in=f_in, f_out=f_out,
            reduce=str(reduce.value), dtype=str(feat_padded.dtype),
            quantized=bool(quantized), backend=backend))

    # Shard routing (see shard_scope): the feature-dim partition applies to
    # every stage whose epilogue is linear in the aggregated features — the
    # int8 MVM's per-tensor activation scale is a global max, so quantized
    # sites stay on the single-device lowering (the dst_block strategy of
    # aggregate_combine_sharded shards those exactly).  A kernel config can
    # veto with shard="none".
    ctx = active_shard_context()
    shard_override = getattr(cfg, "shard", None) if cfg is not None else None
    use_shard = (ctx is not None and ctx.num_shards > 1 and not quantized
                 and shard_override != "none")

    # MAX and the int8 MVM are nonlinear: the combine cannot move through
    # them, so the order is pinned regardless of request or tuner choice.
    # The feature partition likewise pins aggregate-first — it splits the
    # SpMM width and the combine contraction together.
    pinned = reduce == ReduceOp.MAX or quantized or use_shard
    if pinned:
        order = "aggregate_first"
    elif order == "auto" and cfg is not None and getattr(
            cfg, "order", None) in ("aggregate_first", "combine_first"):
        order = cfg.order
    plan = plan_combine_order(bg, f_in, f_out, order)
    _record_plan(bg, f_in, f_out, reduce, backend, plan, quantized)

    block_f = getattr(cfg, "block_f", None) if cfg is not None else None

    if use_shard:
        return _feature_sharded(bg, feat_padded, w, bias, reduce, activation,
                                ctx, block_f)

    if plan.order == "combine_first":
        # Narrow the SpMM width first; the blocked aggregation then runs on
        # whichever backend is active (incl. the unfused Pallas kernel).
        xw = dense_combine(feat_padded, w)
        h = aggregate_blocked(bg, xw, reduce, block_f=block_f)
        if bias is not None:
            h = h + bias
        return _apply_activation(h, activation)

    use_fused = backend == "pallas_fused"
    if use_fused and cfg is not None and getattr(cfg, "fused", None) is not None:
        use_fused = bool(cfg.fused)

    if use_fused:
        # Lazy import: kernels.ops imports core.partition (cycle guard).
        from repro.kernels.ops import fused_block_spmm_padded

        inv_deg = None
        if reduce == ReduceOp.MEAN:
            deg = blocked_degrees(bg).astype(feat_padded.dtype)
            inv_deg = 1.0 / jnp.maximum(deg, 1.0)
        lane = getattr(cfg, "lane", None) if cfg is not None else None
        out = fused_block_spmm_padded(
            bg.blocks, bg.block_row, bg.block_col, feat_padded, w, bias,
            inv_deg, bg.num_dst_groups,
            activation=activation if activation else "none",
            reduce="max" if reduce == ReduceOp.MAX else "sum",
            quantized=bool(quantized),
            lane=lane or 128,
        )
        return out.astype(feat_padded.dtype)

    h = aggregate_blocked(bg, feat_padded, reduce, block_f=block_f)
    return dense_combine(h, w, bias, activation, quantized)


def to_dst_rows(x: jax.Array, pad_dst: int) -> jax.Array:
    """Pad-or-slice a source-padded [G_src*N, ...] array to [G_dst*V, ...].

    Node i is row i on both sides, so row i of the result is node i's."""
    need = pad_dst - x.shape[0]
    if need > 0:
        x = jnp.pad(x, ((0, need),) + ((0, 0),) * (x.ndim - 1))
    return x[:pad_dst]


def attention_aggregate_blocked(
    bg: BlockedGraph,
    values_padded: jax.Array,   # [G_src*N, H, F]  (already W-transformed, per head)
    src_scores: jax.Array,      # [G_src*N, H]     a_src . (W h_u)
    dst_scores: jax.Array,      # [G_dst*V, H]     a_dst . (W h_v)
    negative_slope: float = 0.2,
) -> jax.Array:
    """GAT masked-softmax aggregation over N(v) u {v} on the blocked adjacency.

    Computes, per head:  out[v] = sum_{u in N(v) u {v}} alpha_vu val[u],
    alpha = softmax_u(leaky_relu(dst_scores[v] + src_scores[u])) — GHOST's
    GAT pipeline (Section 3.4.2, Fig. 6b).  The self term is each row's own
    score and value (node i is row i on both sides).  Tile values carry
    edge multiplicity (duplicate edges accumulate at partition time), so a
    duplicate weighs twice, as in the edge-list softmax.  A loop already in
    the graph is not an extra neighbour: the self term stands for it once,
    so loop entries are masked out.

    On the ``pallas`` and ``pallas_fused`` backends this runs the blocked
    edge-softmax kernel (``kernels/attention_block_spmm.py``: one online
    softmax per destination row in VMEM, never a ``[B, V, N, H]`` array).
    The ``jnp`` path below is its oracle: a two-pass (segment-max then
    segment-sum) over dense per-tile logits.

    Returns [G_dst*V, H, F].
    """
    if active_aggregate_backend() in _PALLAS_BACKENDS:
        # Lazy import: kernels.ops imports core.partition (cycle guard).
        from repro.kernels.ops import attention_block_spmm_padded

        return attention_block_spmm_padded(
            bg.blocks, bg.block_row, bg.block_col, values_padded, src_scores,
            dst_scores, bg.num_dst_groups, negative_slope=negative_slope)

    heads = values_padded.shape[1]
    f = values_padded.shape[2]
    pad_dst = bg.num_dst_groups * bg.v
    dst_id = (bg.block_row[:, None, None] * bg.v
              + jnp.arange(bg.v)[None, :, None])
    src_id = (bg.block_col[:, None, None] * bg.n
              + jnp.arange(bg.n)[None, None, :])
    mask = (bg.blocks != 0) & (dst_id != src_id)                       # [B,V,N]

    self_logit = jax.nn.leaky_relu(
        dst_scores + to_dst_rows(src_scores, pad_dst), negative_slope)
    self_logit = self_logit.reshape(bg.num_dst_groups, bg.v, heads)
    self_vals = to_dst_rows(values_padded, pad_dst).reshape(
        bg.num_dst_groups, bg.v, heads, f)

    s_src = src_scores.reshape(bg.num_src_groups, bg.n, heads)[bg.block_col]   # [B,N,H]
    s_dst = dst_scores.reshape(bg.num_dst_groups, bg.v, heads)[bg.block_row]   # [B,V,H]
    logits = s_dst[:, :, None, :] + s_src[:, None, :, :]               # [B,V,N,H]
    logits = jax.nn.leaky_relu(logits, negative_slope)
    neg = jnp.asarray(-1e30, logits.dtype)
    logits = jnp.where(mask[..., None], logits, neg)

    # Pass 1: per-destination-row max over tiles and the self term.
    tile_max = logits.max(axis=2)                                      # [B,V,H]
    row_max = jax.ops.segment_max(tile_max, bg.block_row, num_segments=bg.num_dst_groups)
    row_max = jnp.maximum(row_max, self_logit)                         # finite
    m = row_max[bg.block_row][:, :, None, :]                           # [B,V,1,H]

    # Pass 2: exp-sum and weighted value sum, scaled by multiplicity.
    mult = bg.blocks[..., None]                                        # [B,V,N,1]
    p = jnp.where(mask[..., None], mult * jnp.exp(logits - m), 0.0)    # [B,V,N,H]
    p_self = jnp.exp(self_logit - row_max)                             # [G,V,H]
    denom = jax.ops.segment_sum(p.sum(axis=2), bg.block_row,
                                num_segments=bg.num_dst_groups) + p_self

    vals = values_padded.reshape(bg.num_src_groups, bg.n, heads, f)[bg.block_col]  # [B,N,H,F]
    num_partial = jnp.einsum("bvnh,bnhf->bvhf", p, vals)               # [B,V,H,F]
    num = jax.ops.segment_sum(num_partial, bg.block_row, num_segments=bg.num_dst_groups)
    num = num + p_self[..., None] * self_vals

    out = num / denom[..., None]
    return out.reshape(pad_dst, heads, f)


# ---------------------------------------------------------------------------
# Sharded execution: one blocked aggregate+combine stage across a 1-D device
# mesh.  Two partition strategies, mirroring the two dimensions of the fused
# SpMM (the scaling lever both GNN-acceleration surveys in PAPERS.md name —
# partition-parallel execution across compute units):
#
#   "dst_block" — partition by destination block-row.  Each device owns a
#       contiguous slice of destination groups plus the CSR-sorted edge
#       tiles targeting them (owner-exclusive: block_row is non-decreasing,
#       so one host-side pass splits the tile list).  No cross-device
#       collective is needed for SUM/MEAN/MAX — every destination row is
#       reduced entirely on its owner — and per-device tile order equals
#       the single-device order, so outputs are BIT-EXACT vs the unsharded
#       forward on every backend (the one exception: quantized sites whose
#       per-device lowering is the *unfused* per-tensor int8 combine — its
#       activation scale spans all rows; the fused per-row-block epilogue
#       shards exactly).  Requires host-side prep (``shard_blocked``).
#
#   "feature" — partition the combine contraction.  Each device owns an
#       F_in slice of the features and the matching combine-weight rows;
#       the SpMM runs at F_in/D width per device and one ``psum`` over the
#       contracted dimension rebuilds [G_dst*V, F_out].  Association order
#       of the psum differs from the single-device matmul, so outputs agree
#       to a few ULP (documented tolerance).  Needs no graph resharding, so
#       it drops into existing traces — including vmapped serving
#       executors — via ``shard_scope``.
# ---------------------------------------------------------------------------

SHARD_STRATEGIES = ("auto", "dst_block", "feature")


class ShardedBlockedGraph(NamedTuple):
    """A BlockedGraph re-tiled for a D-way destination-block partition.

    Device d owns destination groups [d*local, (d+1)*local) of a group
    space padded up to ``num_shards * local_dst_groups`` (the pad groups
    receive no tiles and their output rows are sliced off again).  Tile
    slots are padded per shard to ``tile_cap`` with all-zero tiles — exact
    no-ops for every reduce mode — and ``block_row`` is rebased to
    device-LOCAL group ids (still non-decreasing per shard, preserving the
    CSR-sortedness the Pallas kernels require).  ``block_col`` stays
    global: source features are replicated.
    """

    blocks: jax.Array       # [D, Bcap, V, N]
    block_row: jax.Array    # [D, Bcap] int32, device-local dst groups
    block_col: jax.Array    # [D, Bcap] int32, global src groups
    deg: jax.Array          # [D, local_dst_groups * V] MEAN degrees
    num_shards: int
    local_dst_groups: int
    num_dst_groups: int     # global, unpadded
    num_src_groups: int
    v: int
    n: int
    num_nodes: int
    num_blocks: int         # global, unpadded tile count (order planning)

    @property
    def tile_cap(self) -> int:
        return int(self.blocks.shape[1])


def shard_blocked(bg: BlockedGraph, num_shards: int,
                  tile_cap: Optional[int] = None) -> ShardedBlockedGraph:
    """Host-side destination-block partition of a BlockedGraph.

    Splits the CSR-sorted tile list by destination-group owner (a
    contiguous slice per shard), pads every shard to ``tile_cap`` tiles
    (default: the busiest shard's count) with zero tiles, and rebases
    ``block_row`` to device-local ids.  Pure numpy — this is preprocessing,
    the sharded analogue of ``serving.bucketing.pad_partition_to_bucket``.
    """
    if num_shards < 1:
        raise ValueError("num_shards must be >= 1")
    blocks = np.asarray(bg.blocks)
    row = np.asarray(bg.block_row)
    col = np.asarray(bg.block_col)
    gd = bg.num_dst_groups
    local = -(-gd // num_shards)          # ceil: pad groups, never drop any
    owner = np.minimum(row // local, num_shards - 1)
    counts = np.bincount(owner, minlength=num_shards)
    need = max(int(counts.max()), 1)
    if tile_cap is None:
        tile_cap = need
    elif tile_cap < need:
        raise ValueError(f"tile_cap {tile_cap} < busiest shard ({need} tiles)")
    sb = np.zeros((num_shards, tile_cap) + blocks.shape[1:], blocks.dtype)
    # Padding tiles keep per-shard block_row non-decreasing (last local
    # group) and block_col in range; all-zero tiles contribute nothing.
    sr = np.full((num_shards, tile_cap), local - 1, np.int32)
    sc = np.full((num_shards, tile_cap), bg.num_src_groups - 1, np.int32)
    for d in range(num_shards):
        sel = owner == d
        k = int(counts[d])
        sb[d, :k] = blocks[sel]
        sr[d, :k] = row[sel] - d * local
        sc[d, :k] = col[sel]
    deg = np.zeros((num_shards * local * bg.v,), np.float32)
    deg[: gd * bg.v] = np.asarray(blocked_degrees(bg))
    return ShardedBlockedGraph(
        blocks=jnp.asarray(sb),
        block_row=jnp.asarray(sr),
        block_col=jnp.asarray(sc),
        deg=jnp.asarray(deg.reshape(num_shards, local * bg.v)),
        num_shards=num_shards,
        local_dst_groups=local,
        num_dst_groups=gd,
        num_src_groups=bg.num_src_groups,
        v=bg.v,
        n=bg.n,
        num_nodes=bg.num_nodes,
        num_blocks=int(blocks.shape[0]),
    )


class ShardPlan(NamedTuple):
    """Static cost sketch behind one strategy decision (roofline inputs)."""

    strategy: str            # "dst_block" | "feature"
    num_shards: int
    psum_bytes: int          # collective traffic per stage (0 = none)
    bit_exact: bool          # vs the single-device blocked forward

    def to_dict(self) -> dict:
        return dict(self._asdict())


def plan_shard_strategy(num_dst_groups: int, v: int, f_out: int,
                        num_shards: int, *, reduce: ReduceOp = ReduceOp.SUM,
                        quantized: bool = False,
                        sharded_graph: bool = False,
                        strategy: str = "auto") -> ShardPlan:
    """Choose the partition strategy from static shape facts.

    The destination-block partition wins whenever host-prepped tiles are
    available (``sharded_graph``): it moves no bytes between devices and is
    bit-exact.  The feature partition is the fallback that needs no prep
    but pays one fp32 ``psum`` of the [G_dst*V, F_out] output per stage.
    Quantized stages only shard destination-wise (the per-tensor int8
    activation scale does not decompose over feature slices).
    """
    if strategy not in SHARD_STRATEGIES:
        raise ValueError(f"unknown shard strategy '{strategy}'; "
                         f"expected one of {SHARD_STRATEGIES}")
    if strategy == "auto":
        strategy = "dst_block" if (sharded_graph or quantized) else "feature"
    if strategy == "feature" and quantized:
        raise ValueError("quantized stages cannot use the feature partition "
                         "(per-tensor int8 scale is a global reduction); "
                         "prep a ShardedBlockedGraph for dst_block instead")
    psum = (0 if strategy == "dst_block"
            else num_dst_groups * v * f_out * 4 * max(num_shards - 1, 0))
    return ShardPlan(strategy=strategy, num_shards=num_shards,
                     psum_bytes=psum,
                     bit_exact=strategy == "dst_block" and not quantized)


def _feature_sharded(bg: BlockedGraph, feat_padded: jax.Array, w: jax.Array,
                     bias: Optional[jax.Array], reduce: ReduceOp,
                     activation: Optional[str], ctx: ShardContext,
                     block_f: Optional[int]) -> jax.Array:
    """Feature-dim partition: SpMM over an F_in slice per device, psum over
    the contracted combine dimension.  Works under vmap/jit (all operands
    are explicit shard_map arguments, so outer batching rules apply)."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    d = ctx.num_shards
    f_in = int(feat_padded.shape[-1])
    f_out = int(w.shape[-1])
    pad = (-f_in) % d
    # Zero feature columns x zero weight rows are exact no-ops for SUM/MEAN
    # (0 contribution) and for MAX (all-zero columns aggregate to 0, then
    # meet zero weight rows), so padding F_in to a shard multiple is safe.
    featp = jnp.pad(feat_padded, ((0, 0), (0, pad)))
    wp = jnp.pad(w.astype(feat_padded.dtype), ((0, pad), (0, 0)))
    bias_row = (jnp.zeros((f_out,), feat_padded.dtype) if bias is None
                else bias.astype(feat_padded.dtype))
    deg = blocked_degrees(bg).astype(feat_padded.dtype)
    axis = ctx.axis

    def body(blocks, row, col, dg, featl, wl, bias_l):
        lbg = BlockedGraph(
            blocks=blocks, block_row=row, block_col=col,
            num_dst_groups=bg.num_dst_groups,
            num_src_groups=bg.num_src_groups,
            v=bg.v, n=bg.n, num_nodes=bg.num_nodes, deg=dg)
        # MEAN normalizes per destination row — exact on a column slice.
        agg = aggregate_blocked(lbg, featl, reduce, block_f=block_f)
        partial = agg.astype(jnp.float32) @ wl.astype(jnp.float32)
        out = jax.lax.psum(partial, axis)
        # Bias and activation apply once, after the contraction completes;
        # every device computes the same replicated value.
        return _apply_activation(out + bias_l.astype(out.dtype), activation)

    fn = shard_map(
        body, ctx.mesh,
        in_specs=(P(), P(), P(), P(),            # graph replicated
                  P(None, axis),                 # feature columns split
                  P(axis, None),                 # matching weight rows
                  P()),
        out_specs=P(),
        check_rep=False)
    out = fn(bg.blocks, bg.block_row, bg.block_col, deg, featp, wp, bias_row)
    return out.astype(feat_padded.dtype)


def aggregate_combine_sharded(
    graph,                          # ShardedBlockedGraph | BlockedGraph
    feat_padded: jax.Array,         # [G_src * N, F_in] (replicated)
    w: jax.Array,                   # [F_in, F_out]
    bias: Optional[jax.Array] = None,
    *,
    mesh,
    axis: str = "data",
    reduce: ReduceOp = ReduceOp.SUM,
    activation: Optional[str] = None,
    order: str = "auto",
    quantized: bool = False,
    strategy: str = "auto",
) -> jax.Array:
    """One aggregate+combine stage partitioned across a 1-D device mesh.

    ``graph`` selects the partition: a ``ShardedBlockedGraph`` (from
    ``shard_blocked``) runs the destination-block strategy — owner-exclusive
    destination rows, no collectives, bit-exact vs the single-device
    ``aggregate_combine_blocked`` on every backend; a plain ``BlockedGraph``
    runs the feature-dim strategy (psum over the contracted combine
    dimension, few-ULP tolerance).  The active aggregate backend and any
    installed kernel-config resolver apply inside each device's local
    lowering, so the fused epilogue kernel and tuned tile widths carry over
    per shard unchanged.

    Returns [G_dst * V, F_out] (global, padding groups sliced off).
    """
    sharded = isinstance(graph, ShardedBlockedGraph)
    plan = plan_shard_strategy(
        graph.num_dst_groups, graph.v, int(w.shape[-1]),
        int(mesh.shape[axis]), reduce=reduce, quantized=quantized,
        sharded_graph=sharded, strategy=strategy)
    if plan.strategy == "feature":
        if sharded:
            raise ValueError("feature strategy takes a plain BlockedGraph "
                             "(source features are partitioned, not tiles)")
        ctx = ShardContext(mesh=mesh, axis=axis)
        if ctx.num_shards == 1:
            return aggregate_combine_blocked(
                graph, feat_padded, w, bias, reduce=reduce,
                activation=activation, order=order, quantized=quantized)
        return _feature_sharded(graph, feat_padded, w, bias, reduce,
                                activation, ctx, None)
    if not sharded:
        raise ValueError("dst_block strategy needs a ShardedBlockedGraph "
                         "(host-side prep: shard_blocked(bg, num_shards))")
    return _dst_block_sharded(graph, feat_padded, w, bias, reduce,
                              activation, order, quantized, mesh, axis)


def _dst_block_sharded(sbg: ShardedBlockedGraph, feat_padded: jax.Array,
                       w: jax.Array, bias: Optional[jax.Array],
                       reduce: ReduceOp, activation: Optional[str],
                       order: str, quantized: bool, mesh, axis) -> jax.Array:
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    d = int(mesh.shape[axis])
    if d != sbg.num_shards:
        raise ValueError(f"graph was sharded {sbg.num_shards}-way but mesh "
                         f"axis '{axis}' has {d} devices")
    f_in = int(feat_padded.shape[-1])
    f_out = int(w.shape[-1])
    # Resolve the execution order from the GLOBAL geometry, so every
    # device lowers the same order the single-device forward would pick —
    # a per-shard FLOP plan could flip the decision and break bit-exactness.
    if reduce == ReduceOp.MAX or quantized:
        order = "aggregate_first"
    elif order == "auto":
        order = _plan_order_from_geom(
            sbg.num_blocks, sbg.v, sbg.n, sbg.num_dst_groups,
            sbg.num_src_groups, f_in, f_out)
    local = sbg.local_dst_groups
    bias_arg = [] if bias is None else [bias]

    def body(blocks, row, col, dg, featl, wl, *bias_l):
        lbg = BlockedGraph(
            blocks=blocks[0], block_row=row[0], block_col=col[0],
            num_dst_groups=local, num_src_groups=sbg.num_src_groups,
            v=sbg.v, n=sbg.n, num_nodes=local * sbg.v, deg=dg[0])
        # Suppress any enclosing shard_scope: the per-device body IS the
        # sharded lowering; recursing into the feature router would nest
        # shard_maps.
        with shard_scope(None):
            return aggregate_combine_blocked(
                lbg, featl, wl, bias_l[0] if bias_l else None,
                reduce=reduce, activation=activation, order=order,
                quantized=quantized)

    fn = shard_map(
        body, mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis),   # owner-split graph
                  P(), P()) + tuple(P() for _ in bias_arg),
        out_specs=P(axis),
        check_rep=False)
    out = fn(sbg.blocks, sbg.block_row, sbg.block_col, sbg.deg,
             feat_padded, w, *bias_arg)
    # Padding destination groups (group-count rounding) are sliced off.
    return out[: sbg.num_dst_groups * sbg.v]
