"""Multi-model bucketed continuous-batching GNN serving engine.

One engine instance serves a heterogeneous *catalog* of GNN models
(GCN/GraphSAGE/GAT/GIN, differing tasks, feature widths, quantization) on
one substrate — the serving-side analogue of GHOST's versatility claim
(paper Section 4.1).  The engine is a thin orchestrator over four seams:

  registry + executor pool (serving/registry.py)
      named ``ModelEntry`` catalog (each optionally carrying an ``slo_ms``
      latency contract); one jit trace per ``(model_id, bucket)`` so the
      compilation count stays bounded at |models| x |buckets|.
  scheduler (serving/scheduler.py)
      requests wait grouped by ``(model_id, bucket)``; a pluggable policy
      (head-of-line FIFO, occupancy-greedy with a wall-clock
      anti-starvation bound, or SLO-aware EDF/least-slack deadline
      scheduling) picks the group each serve iteration.
  admission control (serving/admission.py)
      optional bound on the waiting queue with reject / shed overload
      policies (the shed victim is the waiting request with the least
      salvageable slack); outcomes surface in the serve report.
  preprocessing cache (serving/cache.py)
      partition + fetch order generated once per distinct structure
      (paper Section 3.4.1) and shared across every model in the catalog
      that uses the same prepare transform.

Each serve iteration gathers up to ``slots`` waiting requests from the
chosen group, stacks their bucket-padded tile arrays into ``[R, B, V, N]``
(features into ``[R, rows, bucket.f]``), and runs one vmapped blocked
forward — via the jnp oracle, the unfused Pallas ``block_spmm`` kernel, or
the fused aggregate+combine ``fused_block_spmm`` kernel with
combination-order planning (``backend="pallas_fused"``; interpret mode on
CPU).

Two driving modes share every scheduling/execution code path:

  tick-driven (the original mode, still what tests and closed-loop
      benchmarks use): the caller invokes ``step()``/``drain()``/``run()``
      and nothing happens between calls.
  always-on (``start()``): background serve threads form and execute
      batches continuously while any number of client threads call
      ``submit``/``try_submit``/``submit_nodes`` concurrently; results are
      picked up with the blocking ``result(rid)`` (or non-blocking
      ``take_result``), and ``stop(drain=True)`` closes intake, joins the
      loop, and serves out the remaining queue.  ``step`` and ``run``
      refuse to run while the loop owns batch formation.

Always-on pipeline (``pipeline_depth``): the loop is a two-stage
pipeline, the serving-side analogue of GHOST's vertex/edge stage overlap
(paper Section 4.4).  A *stacker* thread extracts the scheduler-chosen
batch and stacks its bucket-padded tiles into device-shaped numpy arrays;
``pipeline_depth`` *executor* threads (default 2) pull stacked batches
from a bounded handoff queue (``maxsize=pipeline_depth``), run the device
call, and write results back — so host stacking of batch k+1 overlaps
device execution of batch k instead of serializing behind it, and (with
two workers) host readout/record-building of batch k-1 overlaps both.
``pipeline_depth=0`` degenerates to the PR-9 single-thread serial loop
(one thread does extract → stack → execute → writeback in order).

Concurrency model and locking invariants (two-stage pipeline):

  * One *engine lock* guards all queue/result/metric state: the waiting
    groups, ``results``/``records``, admission + shed bookkeeping, the
    service-time EWMAs, and the writeback tickets.  Two condition
    wait-sets share it: ``_cond`` (submit/publish/drain state changes)
    and ``_write_cond`` (notified only when a batch publishes, so
    ticket-waiting workers are not woken by every submit in a storm).
    Batch *extraction* (stacker) and result *writeback* (executor
    workers) run under the lock; the expensive parts — preprocessing,
    host stacking, device calls, readout — run outside it, so
    submitters are never blocked behind a device call.
  * The preprocessing cache carries its own internal lock; the engine
    lock and the cache lock are **never held simultaneously** (cache
    calls happen strictly outside the engine lock, on the submit path
    and in the unlocked part of writeback's hardware costing).
  * Admission decisions are taken inside the same critical section as
    the queue mutation they authorize, so the waiting bound cannot
    overshoot under concurrent submitters — and the service-time
    admission estimate reads queue depth in that same section.
  * The stacker → executor handoff is a bounded ``queue.Queue`` with its
    own internal lock, never held together with the engine lock (puts
    and gets happen outside it).  So is the free list of stacking buffer
    sets: the stacker takes a set, the executor worker gives it back
    once the batch's output is on the host.
  * Device execution serializes behind a dedicated *device lock*: one
    device runs one program at a time, and concurrent XLA CPU executions
    additionally thrash the shared intra-op thread pool (measurably
    slower than serial).  Workers therefore overlap only *host* work —
    readout, record building, ordered writeback — around the serialized
    device stage.  The copy in runs in that stage too: a batch with empty
    slots writes its filled ones into the pool's device slot set of its
    shape, which the next batch of the shape overwrites, so no run can
    read a set while it is written.  The device lock is held with no
    other lock but the pool's own, which guards its dictionaries.
  * Group-ordered writeback: extraction stamps each batch with a
    monotone per-``(model_id, bucket)`` *ticket* (under the engine
    lock); an executor worker publishes its batch only when the group's
    writeback counter reaches its ticket, waiting on the engine
    condition otherwise.  Two workers may therefore *execute* batches of
    the same group concurrently, but they *publish* in extraction order,
    so ``records`` ordering — and everything derived from it — matches
    the serial loop exactly.  Result *values* need no ordering at all:
    outputs are batch-composition-independent (see the numerics note
    below), which is why overlapping execution stays bit-exact.
  * Intake close: ``stop()`` atomically sets the intake-closed flag with
    the loop-stop flag under the engine lock *before* joining threads
    and draining, so a ``try_submit`` racing ``stop(drain=True)`` either
    enqueued in time (and is served by the final drain) or fails fast
    with ``RuntimeError`` — it can never strand a request behind a dead
    serve thread.  ``start()`` reopens intake.

Executor numerics: zero padding tiles, rows, and feature columns are exact
no-ops (see serving/bucketing.py; executors slice features back to the
model's true ``f_in`` inside the trace), so per-request outputs match the
per-model unbatched *jitted* ``model.apply_blocked`` value-for-value at
fp32, for every model in the catalog, *regardless of batch composition* —
which is also why the always-on loop is bit-exact with the tick loop for
an identical request set.  (Eager, un-jitted execution can differ from any
jitted run by 1 ULP in GAT's softmax — XLA fuses the exp/divide chain
differently — so the jitted unbatched forward is the reference; batching
and bucket padding themselves add no drift.)

Service-time model: writeback feeds an EWMA of observed batch service
time (host stacking + device execution) per ``(model_id, bucket)``,
skipping each key's first execution so jit compilation never pollutes the
steady-state estimate.  The model drives three consumers: (a)
*service-time admission* — a request whose SLO cannot be met even if its
group were scheduled immediately (non-preemptible in-flight batches plus
queue-ahead batches times expected service time already overrun the
deadline) is rejected at enqueue instead
of being served late or shed later; (b) the deadline scheduler's urgency
margin (a group whose head slack is inside one expected service time is
urgent); (c) ``EngineRouter`` routes to the replica with the smallest
estimated backlog *time* (queued batches x expected service) instead of
the shortest raw queue.  The EWMAs survive ``reset_metrics`` (they are a
learned model, not a metric) and surface in ``ServeReport``.

Latency accounting uses ``time.perf_counter()`` (monotonic) throughout —
``time.time()`` can step backwards under clock adjustment and produce
negative latencies.  SLO deadlines are absolute perf_counter instants
(``t_submit + slo_ms/1e3``).

Stage budget: each request's record splits submit-to-pickup into
contiguous stages — queue ``wait_s`` (holding ``sample_s`` and
``prep_s``), then the batch's ``stack_s``, ``device_wait_s``, ``h2d_s``
(the copy in of the filled slots, waited on by itself), ``run_s``
(executor call to output on the host) and ``publish_s``, then
``pickup_s`` until ``result()`` hands the answer over.  ``report.Stage`` takes each stamp and, while a
``jax.profiler`` trace runs, spans the stage's work as a ``gnn.<stage>``
host event on the device trace's clock (waiting is never spanned).
"""

from __future__ import annotations

import dataclasses
import math
import queue as queue_mod
import threading
import time
from collections import OrderedDict, deque
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import Graph
from repro.photonic.perf import GhostConfig, OrchFlags, simulate
from repro.serving.admission import AdmissionController, AdmissionStats
from repro.serving.bucketing import (
    Bucket,
    bucket_for,
    next_pow2,
    pad_features_to_bucket,
    pad_partition_to_bucket,
)
from repro.serving.cache import CacheStats, PreprocessCache
from repro.serving.registry import (
    STACK_DTYPES,
    ExecutorPool,
    HostGraphCatalog,
    HostGraphEntry,
    ModelEntry,
    ModelRegistry,
    stack_shapes,
)
from repro.serving.report import (
    RequestRecord,
    ServeReport,
    Stage,
    build_report,
)
from repro.serving.sampler import HostGraph, gcn_sample_prepare, sample_khop
from repro.serving.scheduler import GroupState, make_scheduler


def gcn_prepare(graph: Graph):
    """Standard GCN preprocessing: self-loops + symmetric normalization."""
    g = graph.with_self_loops()
    return g, g.gcn_edge_weights()


class QueueFullError(RuntimeError):
    """``submit`` on a full bounded queue under the 'reject' policy."""


# EWMA smoothing for the per-(model, bucket) service-time model: heavy
# enough to track load shifts within a few batches, light enough that one
# outlier batch does not swing admission decisions.
SERVICE_EWMA_ALPHA = 0.25


class _StackBuffers:
    """One set of host arrays that batches of one shape are stacked into.

    ``shape`` is ``(R, Bp, V, N, padded_src, f)``, which the bucket fixes;
    ``filled`` is how many slots the set's last batch filled.  A refill
    with ``k`` requests overwrites slots ``0..k-1`` and zeroes only
    ``k..filled-1``, so the arrays equal a fresh zero-filled stack byte for
    byte without paying for the zero-fill (or the page faults of fresh
    memory) on every batch.
    """

    __slots__ = ("shape", "blocks", "rows", "cols", "feats", "filled")

    def __init__(self, shape: tuple):
        self.shape = shape
        self.blocks, self.rows, self.cols, self.feats = (
            np.zeros(s, d) for s, d in zip(stack_shapes(shape),
                                           STACK_DTYPES))
        self.filled = 0

    @property
    def arrays(self) -> tuple:
        return self.blocks, self.rows, self.cols, self.feats

    def fill(self, batch) -> None:
        for i, p in enumerate(batch):
            self.blocks[i], self.rows[i], self.cols[i] = (p.blocks,
                                                          p.block_row,
                                                          p.block_col)
            self.feats[i] = p.feat
        for a in self.arrays:
            a[len(batch):self.filled] = 0
        self.filled = len(batch)


class _StackFreeList:
    """The engine's free ``_StackBuffers`` sets, least recently returned
    first.

    ``take`` hands out the most recently returned set of a shape (None
    when there is none); ``give`` returns one, dropping the least recently
    returned set beyond ``cap``.  Its lock is held with no other lock.
    """

    def __init__(self, cap: int):
        self.cap = cap
        self._sets: list[_StackBuffers] = []
        self._lock = threading.Lock()

    def take(self, shape: tuple) -> Optional[_StackBuffers]:
        with self._lock:
            for i in range(len(self._sets) - 1, -1, -1):
                if self._sets[i].shape == shape:
                    return self._sets.pop(i)
        return None

    def give(self, bufs: _StackBuffers) -> None:
        with self._lock:
            self._sets.append(bufs)
            if len(self._sets) > self.cap:
                del self._sets[0]


@dataclasses.dataclass
class _StackedBatch:
    """One extracted batch after host stacking, ready for an executor.

    The handoff unit of the two-stage pipeline: produced by the stacker
    (or inline by the serial path), consumed by an executor worker, which
    returns ``bufs`` to the engine's free list once the output is on the
    host.  ``ticket`` orders writeback within the batch's (model_id,
    bucket) group; ``stack_s`` is the host stacking time that feeds the
    service-time EWMA and the pipeline busy gauges.
    """

    key: tuple
    batch: list
    serve_tick: int
    t_extract: float
    ticket: int
    bufs: _StackBuffers
    reused: bool        # bufs came from the free list, not np.zeros
    t_stacked: float    # perf_counter when stacking ended

    @property
    def stack_s(self) -> float:
        """Extraction to stacked: the batch's stacking stage."""
        return self.t_stacked - self.t_extract


@dataclasses.dataclass
class _Pending:
    rid: int
    model_id: str
    graph: Graph
    bucket: Bucket
    cache_key: str
    cache_hit: bool
    blocks: np.ndarray      # [Bp, V, N] bucket-padded tiles
    block_row: np.ndarray   # [Bp]
    block_col: np.ndarray   # [Bp]
    feat: np.ndarray        # [Gs_p * N, bucket.f]
    t_submit: float         # perf_counter at submission
    seq: int                # global submission order (FIFO age)
    submit_tick: int        # serve iteration at submission (legacy age)
    slo_ms: float = 0.0     # model's latency contract (0 = none)
    deadline_s: float = math.inf  # absolute perf_counter deadline
    # Node-query (neighborhood-sampled) requests only:
    seed_rows: Optional[np.ndarray] = None  # local rows to slice results to
    num_seeds: int = 0
    sample_s: float = 0.0
    prep_s: float = 0.0
    sampled_nodes: int = 0  # real (non-ghost) vertices in the subgraph
    sampled_edges: int = 0
    fanouts_desc: str = ""


class GnnServeEngine:
    """Continuous batching over blocked GNN forwards for a model catalog.

    Construct, ``register`` one model per catalog entry, then ``submit``
    ``(model_id, graph)`` requests — tick-driven via ``step``/``drain``/
    ``run``, or against the always-on loop between ``start()`` and
    ``stop()``.

    Args:
      cfg: GhostConfig — supplies the (V, N) partition group sizes (shared
        by the whole catalog, so structures are partitioned once) and the
        analytic hardware model's architecture point.
      flags: OrchFlags for the analytic hardware model.
      slots: batch width R; every executor call runs exactly R slots (free
        slots are zero-filled) so each (model, bucket) compiles exactly once.
      backend: "jnp" oracle, "pallas" (unfused block_spmm kernel), or
        "pallas_fused" (fused aggregate+combine epilogue kernel with
        combination-order planning) for SUM/MEAN aggregation (MAX and
        attention always take the jnp path inside the trace).
      scheduler: "fifo" | "occupancy" | "deadline" | a Scheduler instance.
      max_waiting: bound on the waiting queue (None = unbounded).
      admission_policy: "reject" (turn the new request away) or
        "shed-oldest" (drop the waiting request with the least salvageable
        slack — submission order when no model carries an SLO — to make
        room).
      pipeline_depth: executor workers behind the always-on loop's
        stacker stage (and the bound on stacked batches in flight between
        the stages).  Default 2: host stacking of batch k+1 overlaps
        device execution of batch k.  0 = the serial single-thread loop
        (stack and execute never overlap).  Tick-driven ``step``/``run``
        are unaffected — they always serve synchronously.  It also caps
        the free list of reused stacking buffer sets at ``2 *
        pipeline_depth + 1``, the most that can be in use at once.
      service_time_admission: when True (default), a request carrying an
        SLO whose deadline cannot be met even if its group were scheduled
        immediately — per the learned expected-service-time EWMA, the
        non-preemptible in-flight batches, and the queue ahead of it —
        is rejected at enqueue (counted in
        ``AdmissionStats.unmeetable``).  Requests are always admitted
        while the (model, bucket) service time is still unknown.
      cache_capacity: LRU capacity of the preprocessing cache.
      tuner: optional ``kernels.autotune.Autotuner`` (duck-typed: needs
        ``resolve(site)`` + ``live_configs()``); the executor pool resolves
        per-shape-class kernel configs through it at trace-build time,
        warm-started from its persisted cache.
      kernel_config: optional explicit ``KernelConfig``-like object applied
        to every kernel site — a deterministic override that beats the
        tuner (what tests pin).
      mesh: optional 1-D device mesh (see ``launch.mesh.make_data_mesh``).
        When given with >1 device on ``shard_axis``, every executor trace
        runs its fp32 layers under ``core.aggregate.shard_scope``: the
        combine contraction is partitioned along the feature dim with a
        psum over the contracted axis (few-ULP drift vs single-device;
        quantized models stay single-device inside the scope because their
        per-tensor activation scale is a global reduction).  The trace key
        is effectively (model_id, bucket, mesh) — one pool is one mesh.
        A 1-device mesh shards nothing and pins the engine to its device.
      shard_axis: mesh axis name the feature partition maps onto.
    """

    def __init__(
        self,
        *,
        cfg: GhostConfig = GhostConfig(),
        flags: OrchFlags = OrchFlags(),
        slots: int = 8,
        backend: str = "jnp",
        scheduler="fifo",
        max_waiting: Optional[int] = None,
        admission_policy: str = "reject",
        pipeline_depth: int = 2,
        service_time_admission: bool = True,
        cache_capacity: int = 256,
        tuner=None,
        kernel_config=None,
        mesh=None,
        shard_axis: str = "data",
    ):
        self.cfg = cfg.validate()
        self.flags = flags.validate()
        self.slots = slots
        self.backend = backend
        if pipeline_depth < 0:
            raise ValueError(
                f"pipeline_depth must be >= 0, got {pipeline_depth}")
        self.pipeline_depth = int(pipeline_depth)
        self.service_time_admission = bool(service_time_admission)
        self.registry = ModelRegistry()
        self.hosts = HostGraphCatalog()
        self.pool = ExecutorPool(slots=slots, backend=backend,  # validates
                                 tuner=tuner, kernel_config=kernel_config,
                                 mesh=mesh, shard_axis=shard_axis)
        self.scheduler = make_scheduler(scheduler)
        self.admission = AdmissionController(max_waiting, admission_policy)
        self.cache = PreprocessCache(cache_capacity)
        self.results: dict[int, np.ndarray] = {}
        self.records: list[RequestRecord] = []
        # rid -> (record, publish stamp) of each result not yet taken, so
        # pickup can stamp the record's pickup_s.
        self._unpicked: dict[int, tuple[RequestRecord, float]] = {}
        self.shed_rids: list[int] = []
        self._shed_set: set[int] = set()
        self._groups: "OrderedDict[tuple, deque[_Pending]]" = OrderedDict()
        self._next_rid = 0
        self._seq = 0
        self._tick = 0
        self._num_waiting = 0
        self._inflight = 0
        self._max_dropped_wait_ticks = 0
        self._max_dropped_wait_s = 0.0
        # One lock guards all mutable engine state above, with two wait
        # sets on it: ``_cond`` for queue/result state changes (submit,
        # publish, drain) and ``_write_cond`` notified only when a batch
        # publishes — ticket-waiting executor workers park on the latter
        # so a submit storm does not wake them 1000x/s for nothing.  See
        # the module docstring for what runs inside vs outside the lock.
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._write_cond = threading.Condition(self._lock)
        self._thread: Optional[threading.Thread] = None
        self._workers: list[threading.Thread] = []
        self._pipe: Optional["queue_mod.Queue[_StackedBatch]"] = None
        self._stacker_done = True
        self._running = False
        self._intake_closed = False
        self._loop_error: Optional[BaseException] = None
        # Group-ordered writeback: extraction issues tickets, writeback
        # publishes when the group's counter reaches its ticket.  Both
        # dicts only ever grow together, so issued == published holds
        # across start/stop cycles.
        self._group_ticket: dict[tuple, int] = {}
        self._group_write: dict[tuple, int] = {}
        # Service-time model + pipeline busy gauges.  The EWMAs (and the
        # warm set that keeps jit compilation out of them) survive
        # reset_metrics: they are a learned model, not a metric.
        self._service_ewma: dict[tuple, float] = {}
        self._warm_keys: set[tuple] = set()
        self._stack_busy_s = 0.0
        self._exec_busy_s = 0.0
        self._stack_sets_allocated = 0
        self._stack_sets_reused = 0
        # Stacking buffer sets: at most one being stacked, pipeline_depth
        # in the handoff queue and pipeline_depth with executor workers
        # are in use at once, so the free list never needs to hold more.
        self._stack_free = _StackFreeList(2 * self.pipeline_depth + 1)
        # One device runs one program at a time: executor workers serialize
        # the jitted call + block_until_ready behind this lock (concurrent
        # XLA CPU executions thrash the shared intra-op thread pool — worse
        # than serial).  Workers overlap everything ELSE: host readout,
        # record building and the ordered writeback of batch k proceed
        # while batch k+1 occupies the device and the stacker forms k+2.
        self._device_lock = threading.Lock()
        # The device id batch spans carry: it tells router replicas apart.
        device = self.pool.device
        if device is None:
            device = (mesh.devices.flat[0] if mesh is not None
                      else jax.devices()[0])
        self._device_id = int(device.id)

    # ------------------------------------------------------------------
    # Catalog.
    # ------------------------------------------------------------------

    def register(self, model_id: str, model, params, **kwargs) -> ModelEntry:
        """Add one model to the catalog (see ModelRegistry.register).

        The engine fills in the sampled-serving counterpart of the standard
        GCN prepare automatically: a model registered with
        ``prepare_fn=gcn_prepare`` gets ``gcn_sample_prepare`` (host-degree
        normalization) unless the caller supplies their own.
        """
        if (kwargs.get("prepare_fn") is gcn_prepare
                and kwargs.get("sample_prepare_fn") is None):
            kwargs["sample_prepare_fn"] = gcn_sample_prepare
        return self.registry.register(model_id, model, params, **kwargs)

    def register_host_graph(self, name: str, host: HostGraph, *,
                            fanouts: Sequence[Optional[int]] = (10, 10),
                            rng_seed: int = 0) -> HostGraphEntry:
        """Register one resident graph for node-query serving.

        ``fanouts`` is the default per-layer sampling budget (len = hop
        count, ``None`` entries = take the full neighborhood); ``rng_seed``
        fixes the deterministic sampling policy, which is what lets hot
        query nodes share partition-cache entries.
        """
        return self.hosts.register(name, host, fanouts=fanouts,
                                   rng_seed=rng_seed)

    # ------------------------------------------------------------------
    # Request intake (safe from any number of client threads).
    # ------------------------------------------------------------------

    @property
    def num_waiting(self) -> int:
        with self._cond:
            return self._num_waiting

    @property
    def running(self) -> bool:
        """True while the always-on serve loop owns batch formation."""
        with self._cond:
            return self._running

    def try_submit(self, model_id: str, graph: Graph) -> Optional[int]:
        """Preprocess (cached) and enqueue one request.

        Returns the rid, or None when admission control rejected it.
        Safe to call concurrently from many client threads.
        """
        entry_m = self.registry[model_id]
        f = graph.node_feat.shape[1]
        if f != entry_m.f_in:
            raise ValueError(
                f"model '{model_id}' expects {entry_m.f_in} features, "
                f"request carries {f}")
        # Fast path: a request the full queue will certainly reject should
        # not pay preprocessing first.  The authoritative decision is the
        # decide() inside _enqueue — atomic with the queue mutation.
        rid = self._open_request()
        if rid is None:
            return None
        t0 = time.perf_counter()
        return self._enqueue(model_id, graph, rid, t0, t0,
                             transform=entry_m.prepare_fn,
                             salt=entry_m.salt, slo_ms=entry_m.slo_ms)

    def _open_request(self) -> Optional[int]:
        """Intake check and early rejection; returns the request's rid.

        The rid is taken before sampling and preprocessing, so their spans
        can carry it; a request rejected later leaves its rid unused.
        """
        with self._cond:
            self._check_intake_open_locked()
            if self.admission.try_reject_early(self._num_waiting):
                return None
            rid = self._next_rid
            self._next_rid += 1
            return rid

    def _check_intake_open_locked(self) -> None:
        """Fail fast on submit-after-stop.  Caller holds the engine lock.

        ``stop()`` closes intake atomically with the loop-stop flag, so a
        submitter racing the shutdown either lands before the close (and
        the final drain serves it) or sees this error — never a silently
        stranded request.  ``start()`` reopens intake.
        """
        if self._intake_closed:
            raise RuntimeError(
                "engine is stopped: submit after stop() — intake is "
                "closed (call start() to reopen, or drain()/step() serve "
                "only what was already queued)")

    def _enqueue(self, model_id: str, graph: Graph, rid: int, t0: float,
                 t_prev: float, *, transform, salt: str, extra: bytes = b"",
                 slo_ms: Optional[float] = None,
                 nq: Optional[dict] = None) -> Optional[int]:
        """Preprocess (cached, outside the engine lock), then atomically
        admit + enqueue.  Returns the rid, or None on rejection.

        ``t0`` is the submit stamp, ``t_prev`` the end of the request's
        previous stage (sampling, or ``t0``), where ``prep_s`` starts.
        Preprocessing precedes the admission decision, so a preprocessing
        failure needs no stats rollback and can never cost a waiting
        victim its slot.
        """
        with Stage("prep", rid=rid) as prep:
            centry, hit = self.cache.get_or_partition(
                graph, self.cfg.v, self.cfg.n,
                transform=transform, salt=salt, extra=extra)
            pg = centry.pg
            shape = centry.extras.get("shape")
            if shape is None:
                # Structural artifacts are feature-width-independent:
                # cache the f=1 bucket + padded tile arrays once per
                # structure and derive the request's full bucket from its
                # feature width.  Concurrent submitters may duplicate this
                # work (deterministic, identical values); "padded" is
                # published before "shape" so a reader that observes shape
                # always finds padded.
                shape = bucket_for(pg)
                centry.extras["padded"] = pad_partition_to_bucket(pg, shape)
                centry.extras["shape"] = shape
            bucket = dataclasses.replace(
                shape, f=next_pow2(graph.node_feat.shape[1]))
            blocks, row, col = centry.extras["padded"]
            feat = pad_features_to_bucket(pg, bucket, graph.node_feat)
        deadline = (t0 + slo_ms / 1e3 if slo_ms else math.inf)
        with self._cond:
            self._check_intake_open_locked()
            if slo_ms and self.service_time_admission:
                # Service-time admission (ROADMAP 1b): reject now if the
                # deadline is unmeetable even under *immediate* scheduling.
                # Two terms no scheduler can reorder away: (a) work already
                # extracted into the pipeline or onto the device — EDF
                # preemption happens at batch *formation*, so in-flight
                # batches are non-preemptible; (b) the request's own group
                # queue, forcing ceil((q+1)/slots) batch services before
                # its result lands.  No estimate yet (cold key) -> admit.
                est = self._expected_service_locked((model_id, bucket))
                if est is not None:
                    ewma = self._service_ewma
                    mean = sum(ewma.values()) / len(ewma) if ewma else est
                    inflight_s = math.ceil(self._inflight / self.slots) * mean
                    ahead = len(self._groups.get((model_id, bucket), ()))
                    done = (time.perf_counter() + inflight_s
                            + (ahead // self.slots + 1) * est)
                    if done > deadline:
                        self.admission.reject_unmeetable()
                        return None
            verdict = self.admission.decide(self._num_waiting)
            if verdict == "reject":
                return None
            if verdict == "shed":
                # Shed only now, with the replacement request viable and
                # the queue still at its bound (same critical section).
                self._shed_victim_locked()
            pending = _Pending(
                rid=rid,
                model_id=model_id,
                graph=graph,
                bucket=bucket,
                cache_key=centry.key,
                cache_hit=hit,
                blocks=blocks,
                block_row=row,
                block_col=col,
                feat=feat,
                t_submit=t0,
                seq=self._seq,
                submit_tick=self._tick,
                slo_ms=float(slo_ms) if slo_ms else 0.0,
                deadline_s=deadline,
                prep_s=prep.t1 - t_prev,
                **(nq or {}),
            )
            self._seq += 1
            self._groups.setdefault((model_id, bucket),
                                    deque()).append(pending)
            self._num_waiting += 1
            self._cond.notify_all()
            return rid

    def submit(self, model_id: str, graph: Graph) -> int:
        """Like try_submit, but raises QueueFullError on rejection."""
        rid = self.try_submit(model_id, graph)
        if rid is None:
            raise QueueFullError(
                f"waiting queue full ({self.admission.max_waiting}) and "
                f"admission policy is '{self.admission.policy}'")
        return rid

    def try_submit_nodes(
        self,
        model_id: str,
        seed_ids: Sequence[int],
        *,
        host: Optional[str] = None,
        fanouts: Optional[Sequence[Optional[int]]] = None,
        rng_seed: Optional[int] = None,
    ) -> Optional[int]:
        """Answer a node query: sample the k-hop neighborhood and enqueue.

        The million-node intake path: ``seed_ids`` are vertex ids in the
        registered ``HostGraph`` (``host=`` names it; omit when exactly one
        is registered).  A multi-seed batch is sampled as **one shared
        subgraph** — one partitioning, one executor slot — and the result
        rows come back sliced per seed, in ``seed_ids`` order, bit-exact
        with each seed's solo submission whenever the sampling hops cover
        the model depth (see serving/sampler.py).  The engine samples the
        seeds' k-hop in-neighborhood (``fanouts``/``rng_seed`` default to
        the host entry's policy), runs the sampled subgraph through the
        ordinary cache / bucketing / executor machinery — identical
        samples content-hash to one partition entry, the hot-node fast
        path — and slices the result to the seed rows.

        Returns the rid, or None when admission control rejected it.
        Safe to call concurrently from many client threads.
        """
        entry_m = self.registry[model_id]
        if entry_m.task != "node":
            raise ValueError(
                f"node queries need a node-task model; '{model_id}' serves "
                f"task='{entry_m.task}'")
        if entry_m.prepare_fn is not None and entry_m.sample_prepare_fn is None:
            raise ValueError(
                f"model '{model_id}' has prepare_fn="
                f"{entry_m.salt or entry_m.prepare_fn!r} but no "
                "sample_prepare_fn: its normalization needs host-degree "
                "bookkeeping to stay well-defined on sampled neighborhoods "
                "(register with sample_prepare_fn=, cf. gcn_sample_prepare)")
        hentry = self.hosts[host if host is not None else self.hosts.sole_id]
        hg = hentry.host
        if hg.num_features != entry_m.f_in:
            raise ValueError(
                f"model '{model_id}' expects {entry_m.f_in} features, host "
                f"graph '{hentry.name}' carries {hg.num_features}")
        rid = self._open_request()
        if rid is None:
            return None
        t0 = time.perf_counter()
        use_fanouts = (hentry.fanouts if fanouts is None
                       else tuple(fanouts))
        use_seed = (hentry.rng_seed if rng_seed is None
                    else int(rng_seed))
        # lcm(V, N)-aligned local numbering: sampled tiles become
        # bitwise restrictions of the full graph's (module docstring of
        # serving/sampler.py), which is what makes full-fanout samples
        # reproduce the full forward bit-exactly at the seeds.
        with Stage("sample", rid=rid) as sampled:
            sample = sample_khop(hg, seed_ids, use_fanouts, use_seed,
                                 align=math.lcm(self.cfg.v, self.cfg.n))
        spf = entry_m.sample_prepare_fn
        # The transform closes over this sample's host vertices (their host
        # degrees set the edge weights), so the cache key must carry the
        # host-id layout: identical local structures over *different* host
        # vertices must not share a partition.  Without a prepare the
        # partition is structure-only and the extra bytes stay empty.
        transform = (lambda g: spf(sample, hg)) if spf is not None else None
        extra = sample.host_ids.tobytes() if spf is not None else b""
        nq = dict(
            seed_rows=sample.seed_rows,
            num_seeds=int(len(sample.seed_rows)),
            sample_s=sampled.t1 - t0,
            sampled_nodes=sample.num_sampled_nodes,
            sampled_edges=sample.num_sampled_edges,
            fanouts_desc="x".join("full" if f is None else str(f)
                                  for f in use_fanouts),
        )
        return self._enqueue(
            model_id, sample.graph, rid, t0, sampled.t1,
            transform=transform,
            salt=f"{entry_m.sample_salt}:{hg.fingerprint}",
            extra=extra, slo_ms=entry_m.slo_ms, nq=nq)

    def submit_nodes(self, model_id: str, seed_ids: Sequence[int],
                     **kwargs) -> int:
        """Like try_submit_nodes, but raises QueueFullError on rejection."""
        rid = self.try_submit_nodes(model_id, seed_ids, **kwargs)
        if rid is None:
            raise QueueFullError(
                f"waiting queue full ({self.admission.max_waiting}) and "
                f"admission policy is '{self.admission.policy}'")
        return rid

    def _shed_victim_locked(self) -> None:
        """Drop the waiting request with the least salvageable slack.

        Group heads suffice: within one group (one model, so one SLO;
        FIFO arrival) the head has the earliest deadline and the lowest
        seq.  Without SLOs every deadline is infinite and the seq
        tie-break reproduces the historical shed-oldest behavior.
        """
        key, dq = min(self._groups.items(),
                      key=lambda kv: (kv[1][0].deadline_s, kv[1][0].seq))
        victim = dq.popleft()
        if not dq:
            del self._groups[key]
        self._num_waiting -= 1
        self.shed_rids.append(victim.rid)
        self._shed_set.add(victim.rid)
        # The victim's wait counts toward the starvation gauges: a policy
        # that quietly dropped its stalest work must not look starvation-free.
        self._max_dropped_wait_ticks = max(
            self._max_dropped_wait_ticks, self._tick - victim.submit_tick)
        self._max_dropped_wait_s = max(
            self._max_dropped_wait_s,
            time.perf_counter() - victim.t_submit)
        self._cond.notify_all()  # wake any result(victim.rid) waiter

    # ------------------------------------------------------------------
    # Batch formation + execution (shared by both driving modes).
    # ------------------------------------------------------------------

    def _expected_service_locked(self, key: tuple) -> Optional[float]:
        """Expected batch service time (s) for one (model_id, bucket).

        Caller holds the engine lock.  Exact key first; falls back to the
        mean over the model's other warm buckets (a new bucket of a known
        model behaves like its siblings far more than like nothing); None
        when the model has no warm bucket at all.
        """
        v = self._service_ewma.get(key)
        if v is not None:
            return v
        sibs = [s for k, s in self._service_ewma.items() if k[0] == key[0]]
        if sibs:
            return sum(sibs) / len(sibs)
        return None

    def _extract_locked(self):
        """Pop the scheduler-chosen batch.  Caller holds the lock.

        Returns ``(key, batch, serve_tick, t_extract, ticket)`` or None
        when the queue is empty.  The ticket orders this batch's
        writeback within its group (see the module docstring).
        """
        if not self._groups:
            return None
        now = time.perf_counter()
        states = [
            GroupState(key=key, size=len(dq), head_seq=dq[0].seq,
                       head_wait_ticks=self._tick - dq[0].submit_tick,
                       head_age_s=now - dq[0].t_submit,
                       head_deadline_s=dq[0].deadline_s,
                       head_slack_s=dq[0].deadline_s - now,
                       head_est_service_s=(
                           self._expected_service_locked(key) or 0.0))
            for key, dq in self._groups.items()
        ]
        key = self.scheduler.select(states, self.slots)
        dq = self._groups.get(key)
        if dq is None:
            raise RuntimeError(f"scheduler chose unknown group {key!r}")
        batch = [dq.popleft() for _ in range(min(self.slots, len(dq)))]
        if not dq:
            del self._groups[key]
        self._num_waiting -= len(batch)
        self._inflight += len(batch)
        serve_tick = self._tick
        self._tick += 1
        ticket = self._group_ticket.get(key, 0)
        self._group_ticket[key] = ticket + 1
        return key, batch, serve_tick, now, ticket

    def _stack(self, key, batch, serve_tick: int, t_extract: float,
               ticket: int) -> _StackedBatch:
        """Host stage: stack one extracted batch into device-shaped arrays.

        Runs outside every lock (stacker thread, or inline on the serial
        path) — this is the work the pipeline overlaps with device
        execution.  Fills a free buffer set of the batch's shapes, or a
        new zero-filled one when the free list has none.
        """
        shape = self.pool.stack_shape(key[1])
        bufs = self._stack_free.take(shape)
        reused = bufs is not None
        with Stage("stack", batch=serve_tick, device=self._device_id,
                   reused=reused) as stacked:
            if bufs is None:
                bufs = _StackBuffers(shape)
            bufs.fill(batch)
        return _StackedBatch(
            key=key, batch=batch, serve_tick=serve_tick,
            t_extract=t_extract, ticket=ticket, bufs=bufs, reused=reused,
            t_stacked=stacked.t1)

    def _run_stacked(self, sb: _StackedBatch) -> int:
        """Device stage: execute one stacked batch, then publish in group
        ticket order under the engine lock."""
        model_id, bucket = sb.key
        key = sb.key
        batch = sb.batch
        entry = self.registry[model_id]
        was_warm = key in self._warm_keys
        span = dict(batch=sb.serve_tick, device=self._device_id)
        attn = self._attention_counts(entry, bucket, batch)
        inputs = sb.bufs.arrays
        slots = self.pool.copied_slots(len(batch))
        try:
            with self._device_lock:
                # exec_s is measured inside the lock: pure device
                # occupancy, not time spent queued behind a peer's
                # execution.  It splits into the copy in (h2d, waited on
                # so it is timed on its own) and the run, which ends with
                # the output on the host.  The copy in takes only the
                # filled slots where the pool writes them into its device
                # slot set; the lock keeps a run from reading a set while
                # the next batch writes it.
                t_exec0 = time.perf_counter()
                exe = self.pool.executor(entry, bucket)
                with Stage("h2d", **span, slots=slots) as h2d:
                    args = jax.block_until_ready(self.pool.device_args(
                        entry, *inputs, filled=len(batch)))
                with Stage("run", **span, **attn) as run:
                    out = jax.block_until_ready(exe(*args))
                    devices = tuple(sorted(d.id for d in out.devices()))
                    out = np.asarray(out)
                t_done = run.t1
                exec_s = t_done - t_exec0
        finally:
            # The output is on the host (or the run failed): nothing reads
            # the stacked arrays any more, even where device_put aliased
            # them, so the set can take the next batch.
            self._stack_free.give(sb.bufs)
        stage_s = dict(
            batch=sb.serve_tick,
            stack_s=sb.stack_s,
            stack_reused=sb.reused,
            device_wait_s=t_exec0 - sb.t_stacked,
            h2d_s=h2d.t1 - t_exec0,
            h2d_bytes=sum(a[:slots].nbytes for a in inputs),
            h2d_slots=slots,
            run_s=t_done - h2d.t1,
            **attn,
        )

        results: dict[int, np.ndarray] = {}
        records: list[RequestRecord] = []
        # The span leaves out the ticket wait below; publish_s counts it.
        with Stage("publish", **span):
            for i, p in enumerate(batch):
                valid = out[i][: p.graph.num_nodes]
                if entry.task == "node":
                    # Node queries answer only their seed rows (in query
                    # order); whole-graph requests deliver every row.
                    results[p.rid] = (valid if p.seed_rows is None
                                      else valid[p.seed_rows])
                else:
                    results[p.rid] = np.asarray(entry.model.readout(
                        entry.params, jnp.asarray(valid)))
                hw_lat, hw_e = self._hardware_cost(entry, p)
                latency = t_done - p.t_submit
                records.append(RequestRecord(
                    rid=p.rid,
                    model_id=model_id,
                    num_nodes=p.graph.num_nodes,
                    num_edges=p.graph.num_edges,
                    bucket=bucket.describe(),
                    cache_hit=p.cache_hit,
                    latency_s=latency,
                    batch_size=len(batch),
                    wait_ticks=sb.serve_tick - p.submit_tick,
                    wait_s=sb.t_extract - p.t_submit,
                    hw_latency_s=hw_lat,
                    hw_energy_j=hw_e,
                    slo_ms=p.slo_ms,
                    deadline_s=p.deadline_s,
                    slo_met=(latency * 1e3 <= p.slo_ms if p.slo_ms
                             else None),
                    node_query=p.seed_rows is not None,
                    num_seeds=p.num_seeds,
                    sample_s=p.sample_s,
                    sampled_nodes=p.sampled_nodes,
                    sampled_edges=p.sampled_edges,
                    fanouts=p.fanouts_desc,
                    devices=devices,
                    prep_s=p.prep_s,
                    **stage_s,
                ))
        with self._cond:
            # Publish in extraction order within the group: concurrent
            # workers may *execute* same-group batches out of order (the
            # values cannot differ — outputs are batch-composition-
            # independent), but records/results land serially.  The wait
            # parks on the publish-only condition so submit-storm
            # notifications never wake a ticket-waiting worker.
            while self._group_write.get(key, 0) != sb.ticket:
                if self._loop_error is not None:
                    return 0  # a peer crashed; the engine is failed anyway
                self._write_cond.wait(timeout=0.05)
            with Stage("publish", **span) as published:
                self._group_write[key] = sb.ticket + 1
                for rec in records:
                    rec.publish_s = published.t0 - t_done
                    self._unpicked[rec.rid] = (rec, published.t0)
                self.results.update(results)
                self.records.extend(records)
                self._inflight -= len(batch)
                self._stack_busy_s += sb.stack_s
                self._exec_busy_s += exec_s
                if sb.reused:
                    self._stack_sets_reused += 1
                else:
                    self._stack_sets_allocated += 1
                if was_warm:
                    # First execution of a key includes jit compilation;
                    # keep it out of the steady-state service-time model.
                    service = sb.stack_s + exec_s
                    prev = self._service_ewma.get(key)
                    self._service_ewma[key] = (
                        service if prev is None else
                        SERVICE_EWMA_ALPHA * service
                        + (1.0 - SERVICE_EWMA_ALPHA) * prev)
                else:
                    self._warm_keys.add(key)
                self._cond.notify_all()
                self._write_cond.notify_all()
        return len(batch)

    def _attention_counts(self, entry: ModelEntry, bucket: Bucket,
                          batch) -> dict:
        """``attn_edges``/``attn_entries`` of a batch of an attention
        model (see ``RequestRecord``), or {} for any other model."""
        layers = getattr(entry.model, "attention_layers", 0)
        if not layers:
            return {}
        edges = sum(entry.model.attended_entries(p.graph) for p in batch)
        sweep = self.slots * bucket.num_blocks * bucket.v * bucket.n
        return dict(attn_edges=layers * edges, attn_entries=layers * sweep)

    def _execute(self, key, batch, serve_tick: int, t_extract: float,
                 ticket: int) -> int:
        """Serial path: stack then execute one batch, back to back."""
        return self._run_stacked(
            self._stack(key, batch, serve_tick, t_extract, ticket))

    def step(self) -> int:
        """Serve one batch from the scheduler-chosen (model, bucket) group.

        Tick-driven mode only — raises while the always-on loop is
        running (the loop owns batch formation; submit and pick results
        up instead).  Returns the number of requests served (0 when the
        queue is empty).
        """
        with self._cond:
            if self._running:
                raise RuntimeError(
                    "engine loop is running; step() is tick-driven mode — "
                    "submit requests and pick up results instead")
            extracted = self._extract_locked()
        if extracted is None:
            return 0
        return self._execute(*extracted)

    def _fail_loop(self, e: BaseException) -> None:
        """Record the first crash of any serve thread and stop the loop.

        Every waiter (``result``/``drain``/``stop``) re-raises it as
        ``RuntimeError("serve loop failed")``.
        """
        with self._cond:
            if self._loop_error is None:
                self._loop_error = e
            self._running = False
            self._cond.notify_all()
            self._write_cond.notify_all()

    def _serve_loop(self) -> None:
        """pipeline_depth=0: the serial loop — one thread does extract →
        stack → execute → writeback in order (no stage overlap)."""
        try:
            while True:
                with self._cond:
                    while self._running and not self._groups:
                        self._cond.wait(timeout=0.05)
                    if not self._running:
                        return
                    extracted = self._extract_locked()
                if extracted is not None:
                    self._execute(*extracted)
        except BaseException as e:  # noqa: BLE001 — surfaced to clients
            self._fail_loop(e)

    def _pipe_put(self, sb: _StackedBatch) -> bool:
        """Bounded handoff put that stays responsive to a peer crash.

        Returns False (abandoning the batch) only when the engine already
        failed — the loop error reaches every waiter first.
        """
        while True:
            try:
                self._pipe.put(sb, timeout=0.05)
                return True
            except queue_mod.Full:
                with self._cond:
                    if self._loop_error is not None:
                        return False

    def _stacker_loop(self) -> None:
        """Pipeline stage 1: extract the scheduler-chosen batch and stack
        it on the host, then hand off to the executor workers."""
        try:
            while True:
                with self._cond:
                    while self._running and not self._groups:
                        self._cond.wait(timeout=0.05)
                    if not self._running:
                        return
                    extracted = self._extract_locked()
                if extracted is None:
                    continue
                if not self._pipe_put(self._stack(*extracted)):
                    return
        except BaseException as e:  # noqa: BLE001 — surfaced to clients
            self._fail_loop(e)
        finally:
            # Executor workers drain what's queued, then exit on this flag.
            with self._cond:
                self._stacker_done = True
                self._cond.notify_all()

    def _executor_loop(self) -> None:
        """Pipeline stage 2: execute stacked batches and publish results
        in group ticket order.  ``pipeline_depth`` of these run at once."""
        try:
            while True:
                try:
                    sb = self._pipe.get(timeout=0.05)
                except queue_mod.Empty:
                    with self._cond:
                        if self._loop_error is not None:
                            return
                        # _stacker_done is set after the stacker's last
                        # put, so done + empty means no batch can arrive.
                        if self._stacker_done and self._pipe.empty():
                            return
                    continue
                self._run_stacked(sb)
        except BaseException as e:  # noqa: BLE001 — surfaced to clients
            self._fail_loop(e)

    # ------------------------------------------------------------------
    # Always-on loop lifecycle.
    # ------------------------------------------------------------------

    def start(self) -> "GnnServeEngine":
        """Start the background serve threads (idempotent calls raise).

        After start, any number of client threads may submit concurrently;
        batches form and execute continuously.  With ``pipeline_depth >=
        1`` this spawns the stacker plus that many executor workers; with
        0 a single serial serve thread.  Reopens intake after a prior
        ``stop()``.  Pair with ``stop()``.
        """
        with self._cond:
            if self._thread is not None or self._workers:
                raise RuntimeError("serve loop already running")
            self._running = True
            self._intake_closed = False
            self._loop_error = None
            if self.pipeline_depth == 0:
                self._stacker_done = True  # no pipeline stages
                self._thread = threading.Thread(
                    target=self._serve_loop, name="gnn-serve-loop",
                    daemon=True)
                threads = [self._thread]
            else:
                self._stacker_done = False
                self._pipe = queue_mod.Queue(maxsize=self.pipeline_depth)
                self._thread = threading.Thread(
                    target=self._stacker_loop, name="gnn-serve-stack",
                    daemon=True)
                self._workers = [
                    threading.Thread(target=self._executor_loop,
                                     name=f"gnn-serve-exec-{i}", daemon=True)
                    for i in range(self.pipeline_depth)]
                threads = [self._thread, *self._workers]
            for t in threads:
                t.start()
        return self

    def stop(self, drain: bool = True) -> None:
        """Close intake, join the serve threads; by default serve out the
        remaining queue.

        Intake closes atomically with the loop-stop flag (same critical
        section), *before* the final drain pass — a ``try_submit`` racing
        this either enqueued in time (the drain below serves it) or fails
        fast with RuntimeError; it can never strand a request behind a
        dead serve thread.  ``drain=False`` leaves unserved requests
        waiting (a later ``drain()``/``step()``/``start()`` can still
        serve them — but new submissions need ``start()`` to reopen
        intake).  Re-raises a serve-loop crash, if one happened.
        """
        with self._cond:
            self._intake_closed = True
            self._running = False
            self._cond.notify_all()
            t, self._thread = self._thread, None
            workers, self._workers = self._workers, []
        if t is not None:
            t.join()
        for w in workers:
            w.join()
        with self._cond:
            err = self._loop_error
        if err is not None:
            raise RuntimeError("serve loop failed") from err
        if drain:
            self.drain()

    def drain(self) -> int:
        """Serve until the queue is empty; returns requests served.

        With the loop running this blocks until the loop has emptied the
        queue and finished in-flight batches (the loop does the serving);
        tick-driven it serves synchronously.
        """
        with self._cond:
            if self._running:
                before = len(self.records)
                while ((self._num_waiting or self._inflight)
                       and self._running and self._loop_error is None):
                    self._cond.wait(timeout=0.1)
                if self._loop_error is not None:
                    raise RuntimeError(
                        "serve loop failed") from self._loop_error
                return len(self.records) - before
        total = 0
        while True:
            served = self.step()
            if not served:
                return total
            total += served

    # ------------------------------------------------------------------
    # Result pickup.
    # ------------------------------------------------------------------

    def result(self, rid: int, timeout: Optional[float] = None) -> np.ndarray:
        """Blocking pickup: wait for ``rid`` and pop its result.

        Raises KeyError when the request was shed (or, with the loop
        stopped and the queue idle, when the rid is unknown/already
        taken); TimeoutError when ``timeout`` seconds elapse first;
        RuntimeError when the serve loop crashed.  Note an unknown rid
        against a *running* loop waits until the timeout.
        """
        deadline = (None if timeout is None
                    else time.perf_counter() + timeout)
        with self._cond:
            while True:
                if rid in self.results:
                    return self._pop_result_locked(rid)
                if rid in self._shed_set:
                    raise KeyError(
                        f"request {rid} was shed by admission control")
                if self._loop_error is not None:
                    raise RuntimeError(
                        "serve loop failed") from self._loop_error
                if (not self._running and not self._num_waiting
                        and not self._inflight):
                    raise KeyError(rid)
                if deadline is None:
                    self._cond.wait(timeout=0.1)
                else:
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0:
                        raise TimeoutError(
                            f"result {rid} not ready after {timeout}s")
                    self._cond.wait(timeout=min(remaining, 0.1))

    def take_result(self, rid: int) -> np.ndarray:
        """Pop and return one result (KeyError if absent or already taken).

        Non-blocking; see ``result`` for the waiting variant.  Long-running
        servers should reclaim results as they are consumed: ``results``
        and ``records`` otherwise grow with total traffic, and the
        admission bound only caps the *waiting* queue, not delivered
        output retention.
        """
        with self._cond:
            return self._pop_result_locked(rid)

    def _pop_result_locked(self, rid: int) -> np.ndarray:
        """Pop one result and stamp its record's pickup_s (engine lock
        held)."""
        out = self.results.pop(rid)
        rec, t_published = self._unpicked.pop(rid, (None, 0.0))
        if rec is not None:
            rec.pickup_s = time.perf_counter() - t_published
        return out

    # ------------------------------------------------------------------
    # Closed-loop driver + accounting.
    # ------------------------------------------------------------------

    def run(self, requests) -> ServeReport:
        """Submit a stream, drain, and build the throughput report.

        Tick-driven mode only (raises while the loop runs).  ``requests``
        yields ``(model_id, graph)`` pairs; bare graphs are accepted when
        exactly one model is registered.  With a bounded queue the engine
        interleaves serving with intake instead of rejecting (closed-loop
        semantics; use try_submit for open-loop).
        """
        if self.running:
            raise RuntimeError(
                "engine loop is running; run() is tick-driven mode")
        t0 = time.perf_counter()
        max_waiting = self.admission.max_waiting
        for item in requests:
            if isinstance(item, Graph):
                model_id, graph = self.registry.sole_id, item
            else:
                model_id, graph = item
            # Drain ahead of the bound so closed-loop intake is never
            # rejected (and the reject/shed stats stay pure open-loop
            # signals).
            while max_waiting is not None and self.num_waiting >= max_waiting:
                self.step()
            self.submit(model_id, graph)
        self.drain()
        return self.report(time.perf_counter() - t0)

    def _hardware_cost(self, entry: ModelEntry,
                       p: _Pending) -> tuple[float, float]:
        if entry.spec is None:
            return 0.0, 0.0
        # peek(touch=True): hardware costing revisits the entry on the
        # *serve* path, so it must refresh LRU recency — a structure served
        # often but submitted rarely stays resident (and stats stay submit-
        # path-only: this is not a cache hit).
        centry = self.cache.peek(p.cache_key)
        hw_key = ("hw", entry.model_id)  # per-model: specs differ per entry
        if centry is not None and hw_key in centry.extras:
            return centry.extras[hw_key]
        if centry is not None:
            graph = centry.extras.get("graph", p.graph)
        elif entry.prepare_fn is not None and p.seed_rows is None:
            # Entry evicted between submit and serve: re-derive the executed
            # structure so the hardware numbers don't depend on cache state.
            # (Sampled requests skip this — their transform closed over the
            # sample; the raw subgraph is a fine analytic-cost stand-in.)
            graph, _ = entry.prepare_fn(p.graph)
        else:
            graph = p.graph
        rep = simulate(entry.spec, graph, self.cfg, self.flags,
                       entry.dataset_name)
        cost = (rep.latency, rep.energy)
        if centry is not None:
            centry.extras[hw_key] = cost
        return cost

    def queue_wait_gauges(self) -> tuple[int, float]:
        """(max wait ticks, max wait seconds) over waiting + shed requests.

        The starvation gauges must see requests still waiting (or already
        shed), not just the served ones — a policy that never serves a
        cold group would otherwise report a low max wait.
        """
        with self._cond:
            now = time.perf_counter()
            waiting_ticks = max(
                (self._tick - dq[0].submit_tick
                 for dq in self._groups.values()), default=0)
            waiting_s = max(
                (now - dq[0].t_submit for dq in self._groups.values()),
                default=0.0)
            return (max(waiting_ticks, self._max_dropped_wait_ticks),
                    max(waiting_s, self._max_dropped_wait_s))

    def service_time_ms(self) -> dict[str, float]:
        """Learned expected batch service time (ms) per warm
        ``"model_id/bucket"`` key — the EWMA that drives service-time
        admission, deadline urgency, and router slack balancing."""
        with self._cond:
            return {f"{mid}/{bucket.describe()}": ewma * 1e3
                    for (mid, bucket), ewma in self._service_ewma.items()}

    def queue_pressure(self) -> tuple[float, int]:
        """(estimated backlog seconds, raw waiting count) — one locked read.

        Backlog = per-group queued batches x expected service time, plus
        the in-flight tail; groups with no estimate use the engine-wide
        mean (0 when nothing is warm yet, which degrades router slack
        ordering to the raw-queue-length tie-break).  ``EngineRouter``
        sorts replicas by exactly this tuple.
        """
        with self._cond:
            ewma = self._service_ewma
            mean = sum(ewma.values()) / len(ewma) if ewma else 0.0
            backlog = 0.0
            for key, dq in self._groups.items():
                est = self._expected_service_locked(key)
                backlog += (math.ceil(len(dq) / self.slots)
                            * (mean if est is None else est))
            if self._inflight:
                backlog += math.ceil(self._inflight / self.slots) * mean
            return backlog, self._num_waiting

    def pipeline_stats(self) -> dict:
        """Configured depth + cumulative per-stage busy seconds (a report
        turns these into busy *fractions* of the measured wall clock;
        exec is device occupancy — the device lock serializes execution —
        so overlap shows as exec near 1.0 with stack-busy nonzero), and
        how many published batches were stacked into a newly allocated
        or a reused buffer set."""
        with self._cond:
            return {"depth": self.pipeline_depth,
                    "stack_busy_s": self._stack_busy_s,
                    "exec_busy_s": self._exec_busy_s,
                    "stack_sets_allocated": self._stack_sets_allocated,
                    "stack_sets_reused": self._stack_sets_reused}

    def report(self, wall_s: float) -> ServeReport:
        wait_ticks, wait_s = self.queue_wait_gauges()
        with self._cond:
            records = list(self.records)
        return build_report(records, wall_s, self.cache.stats,
                            self.pool.trace_count, self.backend,
                            scheduler=self.scheduler.name,
                            admission_stats=self.admission.stats,
                            queue_max_wait_ticks=wait_ticks,
                            queue_max_wait_s=wait_s,
                            kernel_configs=self.pool.kernel_configs(),
                            topology=self.pool.topology(),
                            service_time_ms=self.service_time_ms(),
                            pipeline=self.pipeline_stats())

    def reset_metrics(self) -> None:
        """Zero serving metrics while keeping compiled executors, cache
        entries, and the service-time EWMAs (a learned model, not a
        metric) — so benchmarks can warm up and then measure steady
        state."""
        with self._cond:
            self.results.clear()
            self.records.clear()
            self._unpicked.clear()
            self.shed_rids.clear()
            self._shed_set.clear()
            self._max_dropped_wait_ticks = 0
            self._max_dropped_wait_s = 0.0
            self._stack_busy_s = 0.0
            self._exec_busy_s = 0.0
            self._stack_sets_allocated = 0
            self._stack_sets_reused = 0
            self.cache.stats = CacheStats()
            self.admission.stats = AdmissionStats()
