"""Model registry + executor pool: the multi-model seam of the engine.

GHOST's pitch (paper Section 4.1) is one substrate serving GCN / GraphSAGE /
GAT / GIN alike; the serving-side analogue is one engine serving a
heterogeneous *catalog*.  Two pieces:

  * ``ModelRegistry`` — named catalog entries (``ModelEntry``): the model
    object and params plus everything per-model the engine used to take as
    constructor state (task, analytic spec, quantization, prepare
    transform, dataset label, feature width).  Registration fail-fast
    validates the task/model contract.
  * ``ExecutorPool`` — compiled vmapped blocked forwards keyed by
    ``(model_id, Bucket)``.  Each executor is one jit trace; the pool is
    the engine's whole compilation state, so the trace count is bounded by
    |models| x |buckets observed|.

Executors accept feature batches at the *bucket's* padded feature width and
slice back to the model's true ``f_in`` inside the trace — the zero padding
columns never enter the arithmetic, so per-request outputs stay bit-exact
vs the unbatched ``apply_blocked`` while models with different feature
widths share the host-side batching machinery.

A batch that fills ``k`` of its ``R`` slots copies in only those ``k``:
the pool writes them into a device-resident ``[R, ...]`` slot set of the
batch's shape, whose other slots are zero, so the executor reads the same
bytes as a whole zero-filled stack (see ``ExecutorPool.device_args``).
"""

from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict
from typing import Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from repro.core.aggregate import (
    AGGREGATE_BACKENDS,
    BlockedGraph,
    aggregate_backend,
    kernel_config_scope,
    shard_scope,
    with_degrees,
)
from repro.serving.bucketing import Bucket


@dataclasses.dataclass
class ModelEntry:
    """One catalog entry: a model plus its per-model serving config."""

    model_id: str
    model: object
    params: object
    task: str                      # "node" | "graph"
    f_in: int                      # true (unpadded) input feature width
    spec: Optional[object] = None  # GnnModelSpec for analytic hw costing
    quantized: bool = False
    prepare_fn: Optional[Callable] = None
    dataset_name: str = "served"
    # Per-model latency contract: every request for this model carries the
    # deadline ``t_submit + slo_ms`` into scheduling (DeadlineScheduler
    # preempts for at-risk heads, deadline-aware shed drops the least
    # salvageable victim) and into accounting (``RequestRecord.slo_met``,
    # per-model p99-vs-SLO attainment in the serve report).  None = no
    # contract: infinite slack, excluded from attainment.
    slo_ms: Optional[float] = None
    # Sampled-serving counterpart of prepare_fn: maps a
    # ``(SampleResult, HostGraph)`` pair to ``(graph, edge_weights)``, with
    # degree bookkeeping taken from the host graph (subgraph degrees
    # undercount frontier vertices).  Models with no prepare_fn need none;
    # models with a prepare_fn cannot serve node queries without one.
    sample_prepare_fn: Optional[Callable] = None

    @property
    def salt(self) -> str:
        """Cache-key salt: identifies the prepare transform, not the model,
        so models sharing a transform share preprocessing artifacts."""
        return self.prepare_fn.__qualname__ if self.prepare_fn else ""

    @property
    def sample_salt(self) -> str:
        """Cache-key salt for the sampled intake path (distinct from the
        whole-graph path: same raw structure, different transform)."""
        fn = self.sample_prepare_fn
        return "sampled:" + (fn.__qualname__ if fn else "")


class ModelRegistry:
    """Named, validated catalog of servable models."""

    def __init__(self):
        self._entries: "OrderedDict[str, ModelEntry]" = OrderedDict()

    def register(
        self,
        model_id: str,
        model,
        params,
        *,
        task: str = "node",
        spec=None,
        quantized: bool = False,
        prepare_fn: Optional[Callable] = None,
        dataset_name: str = "served",
        f_in: Optional[int] = None,
        sample_prepare_fn: Optional[Callable] = None,
        slo_ms: Optional[float] = None,
    ) -> ModelEntry:
        if model_id in self._entries:
            raise ValueError(f"model_id '{model_id}' already registered")
        if task not in ("node", "graph"):
            raise ValueError(f"unknown task '{task}'")
        if slo_ms is not None and slo_ms <= 0:
            raise ValueError("slo_ms must be positive (or None = no SLO)")
        if task == "graph" and not (hasattr(model, "node_embed_blocked")
                                    and hasattr(model, "readout")):
            raise ValueError(
                "task='graph' needs a model with node_embed_blocked + "
                "readout (e.g. GIN); node-level models serve task='node'")
        if not hasattr(model, "apply_blocked"):
            raise ValueError("model must expose apply_blocked(...)")
        if f_in is None:
            f_in = getattr(model, "f_in", None)
        if f_in is None or f_in < 1:
            raise ValueError("pass f_in= (model has no f_in attribute)")
        entry = ModelEntry(
            model_id=model_id, model=model, params=params, task=task,
            f_in=int(f_in), spec=spec, quantized=quantized,
            prepare_fn=prepare_fn, dataset_name=dataset_name,
            sample_prepare_fn=sample_prepare_fn,
            slo_ms=float(slo_ms) if slo_ms is not None else None)
        self._entries[model_id] = entry
        return entry

    def __getitem__(self, model_id: str) -> ModelEntry:
        entry = self._entries.get(model_id)
        if entry is None:
            raise KeyError(f"unknown model_id '{model_id}'; registered: "
                           f"{list(self._entries)}")
        return entry

    def __contains__(self, model_id: str) -> bool:
        return model_id in self._entries

    def __len__(self) -> int:
        return len(self._entries)

    def __iter__(self) -> Iterator[ModelEntry]:
        return iter(self._entries.values())

    def ids(self) -> list[str]:
        return list(self._entries)

    @property
    def sole_id(self) -> str:
        """The single registered model id (bare-graph request convenience)."""
        if len(self._entries) != 1:
            raise ValueError(
                "bare-graph requests need exactly one registered model; "
                f"registry holds {list(self._entries)}")
        return next(iter(self._entries))


class HostGraphCatalog:
    """Named resident ``HostGraph`` stores for the node-query intake path.

    The model catalog answers *which forward to run*; this catalog answers
    *which graph the query nodes live in*.  Each entry pins the serving
    policy alongside the store — default per-layer fanouts and the rng
    seed — because determinism is what lets hot query nodes resample
    identical subgraphs and share one partition-cache entry.
    """

    def __init__(self):
        self._entries: "OrderedDict[str, HostGraphEntry]" = OrderedDict()

    def register(self, name: str, host, *,
                 fanouts=(10, 10), rng_seed: int = 0) -> "HostGraphEntry":
        if name in self._entries:
            raise ValueError(f"host graph '{name}' already registered")
        fanouts = tuple(fanouts)
        if not fanouts:
            raise ValueError("fanouts must name at least one sampled layer")
        entry = HostGraphEntry(name=name, host=host, fanouts=fanouts,
                               rng_seed=int(rng_seed))
        self._entries[name] = entry
        return entry

    def __getitem__(self, name: str) -> "HostGraphEntry":
        entry = self._entries.get(name)
        if entry is None:
            raise KeyError(f"unknown host graph '{name}'; registered: "
                           f"{list(self._entries)}")
        return entry

    def __contains__(self, name: str) -> bool:
        return name in self._entries

    def __len__(self) -> int:
        return len(self._entries)

    def ids(self) -> list[str]:
        return list(self._entries)

    @property
    def sole_id(self) -> str:
        """The single registered host graph (bare submit_nodes convenience)."""
        if len(self._entries) != 1:
            raise ValueError(
                "submit_nodes without host= needs exactly one registered "
                f"host graph; catalog holds {list(self._entries)}")
        return next(iter(self._entries))


@dataclasses.dataclass
class HostGraphEntry:
    """One resident graph plus its sampling policy."""

    name: str
    host: object               # serving.sampler.HostGraph
    fanouts: tuple             # default per-layer fanouts (None = full)
    rng_seed: int = 0


# A stacked batch's arrays, in executor argument order: blocks, rows,
# cols, feats.
STACK_DTYPES = (jnp.float32, jnp.int32, jnp.int32, jnp.float32)
# Device slot sets the pool keeps, least recently used dropped beyond: one
# set holds a whole [R, ...] stack on the device, so this bounds their
# memory as buckets come and go.
MAX_SLOT_SETS = 4


def stack_shapes(shape: tuple) -> tuple:
    """The four array shapes of a stack of ``shape = (R, Bp, V, N,
    padded_src, f)``: blocks, rows, cols, feats."""
    r, bp, v, n, src, f = shape
    return (r, bp, v, n), (r, bp), (r, bp), (r, src, f)


class _SlotSet:
    """One stacked shape's executor inputs, resident on the device.

    ``arrays`` are blocks, rows, cols and feats at ``[R, ...]``; every
    slot at or past ``filled`` (the occupancy of the set's last batch) is
    zero, as in ``engine._StackBuffers``.  ``zero`` is one zero slot of
    each array: the source that clears a slot without a copy in.
    """

    __slots__ = ("arrays", "zero", "filled")

    def __init__(self, shape: tuple, device):
        shapes = stack_shapes(shape)
        self.arrays = tuple(jax.device_put(np.zeros(s, d), device)
                            for s, d in zip(shapes, STACK_DTYPES))
        self.zero = tuple(jax.device_put(np.zeros(s[1:], d), device)
                          for s, d in zip(shapes, STACK_DTYPES))
        self.filled = 0


class ExecutorPool:
    """Compiled vmapped blocked forwards, one per (model_id, bucket).

    ``backend`` selects the aggregation lowering baked into every trace the
    pool builds: "jnp" (oracle), "pallas" (unfused block_spmm), or
    "pallas_fused" (fused aggregate+combine epilogue kernel; the layer-level
    order planner then decides aggregate-first vs combine-first per layer).

    Per-site kernel configs resolve at trace-build time, in precedence
    order: ``kernel_config`` (one explicit config applied to every site —
    the deterministic override tests pin) beats ``tuner`` (a duck-typed
    ``kernels.autotune.Autotuner``-like object with ``resolve(site)``)
    beats the hardcoded defaults.  With a tuner, ``_build`` first runs the
    forward *abstractly* (``jax.eval_shape``) under a recording resolver to
    enumerate the trace's kernel sites — they are all-static Python values,
    so no compute runs — then tunes each off-trace (plain host timing,
    warm-started from the tuner's persisted cache), and only then builds
    the real jit with a lookup resolver.  Timing never happens inside a
    trace, and a warm cache makes the pre-pass pure lookup.

    Where the pool places each batch on one device (no mesh, or a
    1-device mesh), it also keeps one device slot set per stacked shape
    and one compiled slot writer per shape; see ``device_args``.
    """

    def __init__(self, slots: int, backend: str, *,
                 tuner=None, kernel_config=None, mesh=None,
                 shard_axis: str = "data"):
        if slots < 1:
            raise ValueError("slots must be >= 1")
        if backend not in AGGREGATE_BACKENDS:
            raise ValueError(f"unknown backend '{backend}'; expected one of "
                             f"{AGGREGATE_BACKENDS}")
        if mesh is not None and shard_axis not in mesh.axis_names:
            raise ValueError(f"mesh has no axis '{shard_axis}'; axes are "
                             f"{tuple(mesh.axis_names)}")
        self.slots = slots
        self.backend = backend
        self.tuner = tuner
        self.kernel_config = kernel_config
        # The pool's device topology: every trace it builds is keyed by
        # (model_id, bucket) *within* this mesh — a pool IS one mesh, so
        # the effective trace key is (model_id, bucket, mesh).  A 1-device
        # mesh shards nothing but pins the pool to its device: params and
        # batches are placed there (see ``device_args``), which is how
        # router replicas each own a chip.
        self.mesh = mesh
        self.shard_axis = shard_axis
        self.device = (mesh.devices.flat[0]
                       if mesh is not None and mesh.devices.size == 1
                       else None)
        # A multi-device mesh shards each batch inside the trace, so only
        # a pool that places batches on one device copies in by slot.
        self.copies_slots = mesh is None or mesh.devices.size == 1
        self._placed_params: dict[str, object] = {}
        self._executors: dict[tuple[str, Bucket], Callable] = {}
        self._trace_count = 0
        self._writers: dict[tuple, Callable] = {}
        self._writer_trace_count = 0
        self._slot_sets: "OrderedDict[tuple, _SlotSet]" = OrderedDict()
        # Pipelined serving runs several executor workers; the get-or-build
        # below must not race (a lost race would double-compile and skew
        # trace_count).  Build time under the lock is acceptable: it is
        # paid once per (model, bucket) and concurrent callers of a cold
        # key need the same trace anyway.
        self._lock = threading.Lock()

    @property
    def num_shards(self) -> int:
        if self.mesh is None:
            return 1
        return int(self.mesh.shape[self.shard_axis])

    def topology(self) -> dict:
        """Mesh topology baked into this pool's traces (report surface)."""
        if self.mesh is None:
            return {}
        topo = {
            "num_devices": self.num_shards,
            "mesh_shape": {a: int(s) for a, s in self.mesh.shape.items()},
            "shard_axis": self.shard_axis,
            "strategy": "feature" if self.num_shards > 1 else "none",
        }
        if self.device is not None:
            topo["device"] = int(self.device.id)
        return topo

    def kernel_configs(self) -> dict:
        """Shape-class -> config resolved so far (report surface)."""
        if self.kernel_config is not None:
            cfg = self.kernel_config
            to_dict = getattr(cfg, "to_dict", None)
            return {"*": to_dict() if to_dict else dict(vars(cfg))}
        if self.tuner is not None:
            return self.tuner.live_configs()
        return {}

    @property
    def trace_count(self) -> int:
        return self._trace_count

    @property
    def writer_trace_count(self) -> int:
        """Slot writer traces: one per stacked shape, never one per slot
        index or occupancy."""
        return self._writer_trace_count

    def stack_shape(self, bucket: Bucket) -> tuple:
        """``(R, Bp, V, N, padded_src, f)``: the shape a bucket's batches
        are stacked at."""
        return (self.slots, bucket.num_blocks, bucket.v, bucket.n,
                bucket.padded_src, bucket.f)

    def copied_slots(self, filled: Optional[int]) -> int:
        """Slots copied in for a batch that fills ``filled`` of them: only
        those on a one-device pool with empty slots, else all ``R``."""
        if filled is None or filled >= self.slots or not self.copies_slots:
            return self.slots
        return filled

    def __len__(self) -> int:
        return len(self._executors)

    def keys(self) -> list[tuple[str, Bucket]]:
        """The (model_id, bucket) pairs that have an executor."""
        with self._lock:
            return list(self._executors)

    def device_args(self, entry: ModelEntry, *batch,
                    filled: Optional[int] = None) -> tuple:
        """``(params, *batch)`` as executor arguments, on the pool's device.

        A model's params are placed once, the batch on each call.  A
        pinned pool (1-device mesh) commits them to its device; otherwise
        ``self.device`` is None and they go to JAX's default device, where
        a multi-device mesh shards them inside the trace.

        ``batch`` is a whole host stack (blocks, rows, cols, feats at
        ``[R, ...]``) whose slots from ``filled`` on are zero.  Where
        ``copied_slots(filled)`` is under ``R``, only slots ``0..filled-1``
        are copied in, each written into the shape's device slot set,
        and the slots the set's last batch filled past them are cleared
        from its zero slot: the set then holds the stack's bytes exactly.
        Callers serialize the batches of one pool from this call through
        their run (the engine's device lock), since the next batch of the
        shape writes into the arrays returned here.
        """
        params = self._placed_params.get(entry.model_id)
        if params is None:
            params = self._placed_params.setdefault(
                entry.model_id, jax.device_put(entry.params, self.device))
        k = self.copied_slots(filled)
        if k == self.slots:
            return (params, *jax.device_put(batch, self.device))
        blocks, _, _, feats = batch
        shape = (*blocks.shape, *feats.shape[1:])
        with self._lock:
            writer = self._writer(shape)
            # Taken out while written: a write that raises leaves no
            # half-written set behind, and the next batch starts afresh.
            slot_set = self._slot_sets.pop(shape, None)
        if slot_set is None:
            slot_set = _SlotSet(shape, self.device)
        slots = jax.device_put([tuple(a[i] for a in batch)
                                for i in range(k)], self.device)
        arrays = slot_set.arrays
        for i in range(max(k, slot_set.filled)):
            arrays = writer(arrays, np.int32(i),
                            slots[i] if i < k else slot_set.zero)
        slot_set.arrays, slot_set.filled = arrays, k
        with self._lock:
            self._slot_sets[shape] = slot_set
            while len(self._slot_sets) > MAX_SLOT_SETS:
                self._slot_sets.popitem(last=False)
        return (params, *arrays)

    def _writer(self, shape: tuple) -> Callable:
        """The compiled slot writer of a stacked shape (caller holds the
        lock): ``(arrays, i, slot) -> arrays`` with slot ``i`` of each
        array replaced by ``slot``'s, in place (the arrays are donated).
        ``i`` is traced, so one program serves every slot index."""
        writer = self._writers.get(shape)
        if writer is not None:
            return writer

        def write(arrays, i, slot):
            self._writer_trace_count += 1  # runs at trace time only
            return tuple(jax.lax.dynamic_update_index_in_dim(a, s, i, 0)
                         for a, s in zip(arrays, slot))

        sharding = (None if self.device is None
                    else SingleDeviceSharding(self.device))
        structs = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
                   for s, d in zip(stack_shapes(shape), STACK_DTYPES)]
        writer = self._writers[shape] = jax.jit(
            write, donate_argnums=0).lower(
            tuple(structs),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding),
            tuple(jax.ShapeDtypeStruct(t.shape[1:], t.dtype,
                                       sharding=sharding)
                  for t in structs)).compile()
        return writer

    def lower(self, entry: ModelEntry, bucket: Bucket, sharding=None):
        """Lower the (model, bucket) executor at its batch shapes without
        running it; ``.compile().as_text()`` then shows what the device
        runs.  ``sharding`` places the abstract arguments, e.g. on a
        described (not attached) device for an ahead-of-time compile."""
        def struct(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        params = jax.tree.map(lambda p: struct(p.shape, p.dtype),
                              entry.params)
        return self.executor(entry, bucket).lower(
            params, *(struct(s, d) for s, d in zip(
                stack_shapes(self.stack_shape(bucket)), STACK_DTYPES)))

    def executor(self, entry: ModelEntry, bucket: Bucket) -> Callable:
        """The (model, bucket) executor, built on first use together with
        its shape's slot writer, so neither compiles while serving."""
        key = (entry.model_id, bucket)
        with self._lock:
            exe = self._executors.get(key)
            if exe is None:
                exe = self._executors[key] = self._build(entry, bucket)
                if self.copies_slots:
                    self._writer(self.stack_shape(bucket))
            return exe

    def _build(self, entry: ModelEntry, bucket: Bucket) -> Callable:
        model, task = entry.model, entry.task
        quantized, f_in = entry.quantized, entry.f_in
        backend = self.backend
        # The executor's static node count: padded rows past this are pure
        # padding on both the source and destination sides; per-request
        # validity is handled by host-side slicing.  The graph task runs the
        # blocked *embedding* batch-wide and leaves the sum-pool readout to
        # the per-request path (the fp32 pooled sum depends on row count, so
        # pooling at the bucket shape would break bit-exactness).
        num_nodes = min(bucket.padded_dst, bucket.padded_src)
        # A 1-device mesh is a no-op shard scope; None suppresses sharding
        # entirely, so the trace is identical to the meshless pool's (the
        # pin to its device happens in device_args, outside the trace).
        shard_mesh = self.mesh if self.num_shards > 1 else None
        shard_axis = self.shard_axis

        def make_fwd(resolver, count_trace):
            def fwd(params, blocks, row, col, feat):
                if count_trace:
                    self._trace_count += 1  # runs at trace time only
                feat = feat[:, :f_in]   # strip feature-dim bucket padding
                bg = BlockedGraph(
                    blocks=blocks, block_row=row, block_col=col,
                    num_dst_groups=bucket.num_dst_groups,
                    num_src_groups=bucket.num_src_groups,
                    v=bucket.v, n=bucket.n, num_nodes=num_nodes,
                )
                # Degrees are structure-static: reduce them once per forward
                # so every MEAN layer in the model shares the result (XLA
                # drops the reduction entirely for models that never read it).
                bg = with_degrees(bg)
                # Backend and kernel-config selections are read at trace
                # time, so they bake into this executor's compiled program.
                with aggregate_backend(backend), \
                        kernel_config_scope(resolver), \
                        shard_scope(shard_mesh, shard_axis):
                    if task == "graph":
                        return model.node_embed_blocked(params, bg, feat,
                                                        quantized)
                    return model.apply_blocked(params, bg, feat, quantized)
            return fwd

        resolver = self._resolve_sites(entry, bucket, make_fwd)
        batched = jax.vmap(make_fwd(resolver, count_trace=True),
                           in_axes=(None, 0, 0, 0, 0))
        return jax.jit(batched)

    def _resolve_sites(self, entry: ModelEntry, bucket: Bucket, make_fwd):
        """The trace's kernel-config resolver (None = hardcoded defaults)."""
        if self.kernel_config is not None:
            cfg = self.kernel_config
            return lambda site: cfg
        if self.tuner is None:
            return None
        # Enumerate kernel sites abstractly: eval_shape runs the forward on
        # shape/dtype structs only, so the recording resolver sees every
        # site this trace will hit without executing (or timing) anything.
        # The recording fwd does NOT count as a trace — only the real build
        # below does.
        sites: list = []

        def record(site):
            if site not in sites:
                sites.append(site)
            return None

        struct = jax.ShapeDtypeStruct
        jax.eval_shape(
            make_fwd(record, count_trace=False),
            entry.params,
            struct((bucket.num_blocks, bucket.v, bucket.n), jnp.float32),
            struct((bucket.num_blocks,), jnp.int32),
            struct((bucket.num_blocks,), jnp.int32),
            struct((bucket.padded_src, bucket.f), jnp.float32),
        )
        # Tune (or cache-lookup) each site off-trace, then hand the real
        # trace a pure-lookup resolver over the frozen results.
        resolved = {site: self.tuner.resolve(site) for site in sites}
        return lambda site: resolved.get(site)
