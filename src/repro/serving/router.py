"""Replica router: N serving engines behind one submit/result surface.

One ``GnnServeEngine`` is one executor pool on one mesh — scaling past a
single pool means running several engine *replicas* and routing requests
between them.  ``EngineRouter`` owns that seam:

  placement (catalog-aware)
      "hot" models register on every replica, so any replica can absorb
      their traffic; "cold" models pin to exactly one replica (fewest
      pinned models first), so the long tail of rarely-served models costs
      one trace set instead of N.  Placement is decided at ``register``
      time and never migrates — a model's compiled executors live where
      its traffic lands.
  routing (slack-aware, admission-respecting)
      each request goes to the eligible replica with the smallest
      *estimated completion slack cost*: queued batches times the
      replica's learned expected service time (``engine.queue_pressure``),
      tie-broken by raw queue length.  Two replicas with equal queue
      depths but different catalogs (one serving 2 ms batches, one 40 ms)
      are not equally loaded — time-weighted backlog routes around the
      slow one where raw queue length cannot.  Before any service time is
      learned every backlog estimate is 0.0 and the tie-break reproduces
      shortest-queue routing exactly.  When the chosen replica's
      admission controller rejects, the router falls back through the
      remaining eligible replicas (in the same order) before giving up.
      Every replica keeps its own admission bound — overload on one hot
      replica sheds there without disturbing the others.
  identity (global rids)
      replica-local rids never leak: the router hands out global rids and
      keeps the (replica, local rid) mapping for ``take_result`` /
      ``result``.  The mapping is lock-protected, so routing is safe from
      many client threads (each replica's intake is already thread-safe).
  lifecycle (always-on passthrough)
      ``start()``/``stop()`` start and stop every replica's serve loop;
      ``result(rid)`` blocks on the owning replica.  Tick-driven
      ``step``/``drain``/``run`` remain for closed-loop use.
  accounting (merged + per-replica)
      ``report`` folds every replica's records into one ``ServeReport``
      (same math a single engine would produce for the union stream —
      including SLO attainment, merged across replicas from the union
      record set) and fills ``ServeReport.replicas`` with per-replica
      served counts, admission outcomes, per-replica attainment, and mesh
      topology — the dashboard view of where traffic actually went.

The replicas are plain engines: everything pluggable on an engine
(scheduler, admission policy, backend, tuner, mesh) is pluggable per
router via ``**engine_kwargs``, applied uniformly to every replica.
``meshes=`` overrides that uniformity for device placement — one mesh per
replica, so a host's devices can be split between replicas rather than
shared.  Without a mesh from either, replicas spread over the visible
devices one per device (``replica_meshes``), so N replicas on an N-chip
host each own a chip.
"""

from __future__ import annotations

import threading
import time
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from repro.core.graph import Graph
from repro.serving.admission import AdmissionStats
from repro.serving.cache import CacheStats
from repro.serving.engine import GnnServeEngine, QueueFullError
from repro.serving.report import (
    ServeReport,
    Stage,
    build_report,
    slo_attainment_from,
)
from repro.serving.sampler import HostGraph


def replica_meshes(num_replicas: int, axis: str = "data"):
    """One 1-device mesh per replica, round robin over the visible devices,
    or None when only one device is visible.  A 1-device mesh pins an
    engine to its device (``ExecutorPool.device_args``)."""
    devices = jax.devices()
    if len(devices) == 1:
        return None
    return [Mesh(np.asarray([devices[i % len(devices)]]), (axis,))
            for i in range(num_replicas)]


class EngineRouter:
    """Catalog-aware request router over ``GnnServeEngine`` replicas.

    Args:
      num_replicas: how many engine replicas to build (>= 1).
      meshes: optional sequence of one mesh (or None) per replica, so
        replicas can own disjoint device slices; without it every replica
        shares the ``mesh=`` in ``engine_kwargs`` when that is a mesh, and
        otherwise replica i is pinned to visible device i (round robin;
        one visible device leaves every replica meshless on it).
      engine_kwargs: forwarded verbatim to every ``GnnServeEngine``.
    """

    def __init__(self, num_replicas: int = 2, *, meshes=None, **engine_kwargs):
        if num_replicas < 1:
            raise ValueError("num_replicas must be >= 1")
        if meshes is not None:
            meshes = list(meshes)
            if len(meshes) != num_replicas:
                raise ValueError(
                    f"meshes has {len(meshes)} entries for "
                    f"{num_replicas} replicas")
            if "mesh" in engine_kwargs:
                raise ValueError("pass either meshes= or mesh=, not both")
        elif engine_kwargs.get("mesh") is None:
            engine_kwargs.pop("mesh", None)
            meshes = replica_meshes(num_replicas,
                                    engine_kwargs.get("shard_axis", "data"))
        self.replicas: list[GnnServeEngine] = []
        for i in range(num_replicas):
            kwargs = dict(engine_kwargs)
            if meshes is not None:
                kwargs["mesh"] = meshes[i]
            self.replicas.append(GnnServeEngine(**kwargs))
        # model_id -> tuple of eligible replica indices (len>1 iff hot).
        self._placement: dict[str, tuple[int, ...]] = {}
        # host graph name -> tuple of replica indices holding a copy.
        self._host_placement: dict[str, tuple[int, ...]] = {}
        self._pinned_count = [0] * num_replicas  # cold models per replica
        # global rid -> (replica index, replica-local rid); guarded by
        # _rid_lock so concurrent client threads can route safely.
        self._rid_map: dict[int, tuple[int, int]] = {}
        self._next_rid = 0
        self._rid_lock = threading.Lock()

    @property
    def num_replicas(self) -> int:
        return len(self.replicas)

    # ------------------------------------------------------------------
    # Catalog placement.
    # ------------------------------------------------------------------

    def register(self, model_id: str, model, params, *, hot: bool = False,
                 replica: Optional[int] = None, **kwargs) -> tuple[int, ...]:
        """Place one model and register it on its replica(s).

        hot=True registers on every replica (traffic spreads by load);
        otherwise the model pins to ``replica`` if given, else to the
        replica carrying the fewest pinned models.  Returns the tuple of
        replica indices serving this model.
        """
        if model_id in self._placement:
            raise ValueError(f"model_id '{model_id}' already placed")
        if hot:
            if replica is not None:
                raise ValueError("hot models go to every replica; "
                                 "replica= only applies to cold models")
            where = tuple(range(self.num_replicas))
        else:
            if replica is None:
                replica = int(np.argmin(self._pinned_count))
            if not 0 <= replica < self.num_replicas:
                raise ValueError(f"replica {replica} out of range "
                                 f"[0, {self.num_replicas})")
            self._pinned_count[replica] += 1
            where = (replica,)
        for i in where:
            self.replicas[i].register(model_id, model, params, **kwargs)
        self._placement[model_id] = where
        return where

    def placement(self, model_id: str) -> tuple[int, ...]:
        where = self._placement.get(model_id)
        if where is None:
            raise KeyError(f"unknown model_id '{model_id}'; placed: "
                           f"{list(self._placement)}")
        return where

    def register_host_graph(
        self, name: str, host: HostGraph, *,
        replicas: Optional[Sequence[int]] = None,
        fanouts: Sequence[Optional[int]] = (10, 10),
        rng_seed: int = 0,
    ) -> tuple[int, ...]:
        """Place one resident ``HostGraph`` for node-query serving.

        ``replicas=None`` registers the store on every replica (the numpy
        CSR is host memory, cheap to share in-process); an explicit index
        list pins it — node queries then only route to replicas holding
        the graph.  Returns the tuple of holding replica indices.
        """
        if name in self._host_placement:
            raise ValueError(f"host graph '{name}' already placed")
        if replicas is None:
            where = tuple(range(self.num_replicas))
        else:
            where = tuple(sorted(set(int(i) for i in replicas)))
            if not where:
                raise ValueError("replicas must name at least one replica")
            if where[0] < 0 or where[-1] >= self.num_replicas:
                raise ValueError(f"replica index out of range "
                                 f"[0, {self.num_replicas}): {where}")
        for i in where:
            self.replicas[i].register_host_graph(
                name, host, fanouts=fanouts, rng_seed=rng_seed)
        self._host_placement[name] = where
        return where

    def host_placement(self, name: str) -> tuple[int, ...]:
        where = self._host_placement.get(name)
        if where is None:
            raise KeyError(f"unknown host graph '{name}'; placed: "
                           f"{list(self._host_placement)}")
        return where

    # ------------------------------------------------------------------
    # Request intake and routing.
    # ------------------------------------------------------------------

    @property
    def num_waiting(self) -> int:
        return sum(e.num_waiting for e in self.replicas)

    def try_submit(self, model_id: str, graph: Graph) -> Optional[int]:
        """Route one request; returns a global rid or None when every
        eligible replica's admission controller rejected it."""
        where = self.placement(model_id)
        # Least-estimated-backlog first among eligible replicas (queued
        # batches x learned service time, raw queue length as tie-break —
        # see queue_pressure); on rejection fall back to the next (per-
        # replica admission, router-level failover).  Sort is stable, so
        # fully tied replicas keep placement order.
        with Stage("route"):
            order = sorted(where,
                           key=lambda i: self.replicas[i].queue_pressure())
        for i in order:
            local = self.replicas[i].try_submit(model_id, graph)
            if local is not None:
                return self._alloc_rid(i, local)
        return None

    def _alloc_rid(self, replica: int, local: int) -> int:
        with self._rid_lock:
            rid = self._next_rid
            self._next_rid += 1
            self._rid_map[rid] = (replica, local)
            return rid

    def submit(self, model_id: str, graph: Graph) -> int:
        rid = self.try_submit(model_id, graph)
        if rid is None:
            raise QueueFullError(
                f"all {len(self.placement(model_id))} eligible replicas "
                f"rejected model '{model_id}' (waiting queues full)")
        return rid

    def try_submit_nodes(self, model_id: str, seed_ids, *,
                         host: Optional[str] = None,
                         **kwargs) -> Optional[int]:
        """Route one node query to a replica holding both the model and the
        host graph (least estimated backlog first, admission failover);
        returns a global rid or None when every such replica rejected it."""
        where_m = self.placement(model_id)
        if host is None:
            if len(self._host_placement) != 1:
                raise ValueError(
                    "node queries without host= need exactly one placed "
                    f"host graph; router holds {list(self._host_placement)}")
            host = next(iter(self._host_placement))
        where_h = set(self.host_placement(host))
        eligible = [i for i in where_m if i in where_h]
        if not eligible:
            raise ValueError(
                f"no replica holds both model '{model_id}' ({where_m}) and "
                f"host graph '{host}' ({sorted(where_h)})")
        with Stage("route"):
            order = sorted(eligible,
                           key=lambda i: self.replicas[i].queue_pressure())
        for i in order:
            local = self.replicas[i].try_submit_nodes(
                model_id, seed_ids, host=host, **kwargs)
            if local is not None:
                return self._alloc_rid(i, local)
        return None

    def submit_nodes(self, model_id: str, seed_ids, **kwargs) -> int:
        rid = self.try_submit_nodes(model_id, seed_ids, **kwargs)
        if rid is None:
            raise QueueFullError(
                f"all replicas eligible for node queries on '{model_id}' "
                "rejected the request (waiting queues full)")
        return rid

    # ------------------------------------------------------------------
    # Serving.
    # ------------------------------------------------------------------

    def start(self) -> "EngineRouter":
        """Start every replica's always-on serve loop."""
        for e in self.replicas:
            e.start()
        return self

    def stop(self, drain: bool = True) -> None:
        """Stop every replica's serve loop (draining by default)."""
        errors = []
        for e in self.replicas:
            try:
                e.stop(drain=drain)
            except RuntimeError as exc:  # keep stopping the rest
                errors.append(exc)
        if errors:
            raise errors[0]

    def result(self, rid: int, timeout: Optional[float] = None) -> np.ndarray:
        """Blocking pickup by global rid (see ``GnnServeEngine.result``)."""
        with self._rid_lock:
            replica, local = self._rid_map.pop(rid)
        try:
            return self.replicas[replica].result(local, timeout=timeout)
        except TimeoutError:
            with self._rid_lock:  # not delivered: keep the mapping alive
                self._rid_map[rid] = (replica, local)
            raise

    def step(self) -> int:
        """One tick on every replica with waiting work; returns total served."""
        return sum(e.step() for e in self.replicas if e.num_waiting)

    def drain(self) -> int:
        total = 0
        while True:
            served = self.step()
            if not served:
                return total
            total += served

    def run(self, requests) -> ServeReport:
        """Submit a stream, drain every replica, and build the merged report.

        Mirrors ``GnnServeEngine.run`` closed-loop semantics: when every
        eligible replica is at its admission bound the router serves ticks
        until one frees up instead of rejecting.
        """
        t0 = time.perf_counter()
        for item in requests:
            if isinstance(item, Graph):
                if len(self._placement) != 1:
                    raise ValueError(
                        "bare-graph requests need exactly one placed model; "
                        f"router holds {list(self._placement)}")
                model_id, graph = next(iter(self._placement)), item
            else:
                model_id, graph = item
            while True:
                rid = self.try_submit(model_id, graph)
                if rid is not None:
                    break
                if not self.step():
                    raise RuntimeError(
                        "request rejected with no waiting work to drain")
        self.drain()
        return self.report(time.perf_counter() - t0)

    def take_result(self, rid: int) -> np.ndarray:
        """Pop one result by global rid (KeyError if absent/already taken)."""
        with self._rid_lock:
            replica, local = self._rid_map.pop(rid)
        return self.replicas[replica].take_result(local)

    # ------------------------------------------------------------------
    # Merged accounting.
    # ------------------------------------------------------------------

    def report(self, wall_s: float) -> ServeReport:
        records = []
        cache = CacheStats()
        admission = AdmissionStats()
        per_replica: dict[str, dict] = {}
        wait_ticks, wait_s = 0, 0.0
        for i, e in enumerate(self.replicas):
            replica_records = list(e.records)
            records.extend(replica_records)
            cache.hits += e.cache.stats.hits
            cache.misses += e.cache.stats.misses
            cache.evictions += e.cache.stats.evictions
            admission.admitted += e.admission.stats.admitted
            admission.rejected += e.admission.stats.rejected
            admission.shed += e.admission.stats.shed
            admission.unmeetable += e.admission.stats.unmeetable
            t, s = e.queue_wait_gauges()
            wait_ticks, wait_s = max(wait_ticks, t), max(wait_s, s)
            served: dict[str, int] = {}
            for r in replica_records:
                served[r.model_id] = served.get(r.model_id, 0) + 1
            per_replica[f"replica{i}"] = {
                "served": len(replica_records),
                "per_model": served,
                "admitted": e.admission.stats.admitted,
                "rejected": e.admission.stats.rejected,
                "unmeetable": e.admission.stats.unmeetable,
                "shed": e.admission.stats.shed,
                "slo_attainment": slo_attainment_from(replica_records),
                "traces_compiled": e.pool.trace_count,
                "topology": e.pool.topology(),
                "kernel_configs": e.pool.kernel_configs(),
                "service_time_ms": e.service_time_ms(),
                "pipeline": e.pipeline_stats(),
                "devices": sorted({d for r in replica_records
                                   for d in r.devices}),
            }
        first = self.replicas[0]
        # The merged ServeReport computes union-stream SLO attainment from
        # the concatenated records itself (build_report -> slo_attainment_
        # from), so cross-replica attainment needs no extra merge step.
        return build_report(
            records, wall_s, cache,
            sum(e.pool.trace_count for e in self.replicas),
            first.backend,
            scheduler=first.scheduler.name,
            admission_stats=admission,
            queue_max_wait_ticks=wait_ticks,
            queue_max_wait_s=wait_s,
            kernel_configs=self._merged_kernel_configs(),
            topology=self._merged_topology(),
            replicas=per_replica,
            service_time_ms=self._merged_service_times(),
            pipeline=self._merged_pipeline(),
        )

    def _merged_service_times(self) -> dict:
        """Mean expected service time per key across replicas that know it.

        The replicas run on one host here, so a cross-replica mean is a
        fair summary; replica-exact EWMAs stay in
        ``ServeReport.replicas[...]["service_time_ms"]``.
        """
        sums: dict[str, float] = {}
        counts: dict[str, int] = {}
        for e in self.replicas:
            for key, ms in e.service_time_ms().items():
                sums[key] = sums.get(key, 0.0) + ms
                counts[key] = counts.get(key, 0) + 1
        return {key: sums[key] / counts[key] for key in sums}

    def _merged_pipeline(self) -> dict:
        """Summed per-stage busy seconds and stacking buffer-set counts
        over all replicas (the router's replicas share one configured
        depth; per-replica splits stay in ``ServeReport.replicas``)."""
        stats = [e.pipeline_stats() for e in self.replicas]
        return {
            "depth": stats[0]["depth"],
            **{k: sum(s[k] for s in stats)
               for k in ("stack_busy_s", "exec_busy_s",
                         "stack_sets_allocated", "stack_sets_reused")},
        }

    def _merged_kernel_configs(self) -> dict:
        """Union of every replica's live kernel configs.

        Taking replica 0's view alone would silently drop everything
        replicas 1..N-1 compiled (per-replica tuners resolve their own
        winners; heterogeneous pools can pin different overrides).  Keys
        agreeing across replicas merge; a key whose config *differs* from
        the one already merged is kept under a ``replicaI:`` prefix so no
        resolution is lost.  Full per-replica views live in
        ``ServeReport.replicas[...]["kernel_configs"]``.
        """
        merged: dict = {}
        for i, e in enumerate(self.replicas):
            for key, cfg in e.pool.kernel_configs().items():
                if key not in merged:
                    merged[key] = cfg
                elif merged[key] != cfg:
                    merged[f"replica{i}:{key}"] = cfg
        return merged

    def _merged_topology(self) -> dict:
        """One topology when the replicas agree; an aggregate otherwise.

        Uniform replicas (the common case) report their shared mesh
        unchanged.  With per-replica meshes the merged view sums the
        device counts and marks itself heterogeneous — per-replica meshes
        stay in ``ServeReport.replicas[...]["topology"]``.
        """
        topos = [e.pool.topology() for e in self.replicas]
        if not any(topos):
            return {}
        if all(t == topos[0] for t in topos):
            return dict(topos[0])
        return {
            # A replica without a mesh still occupies one device.
            "num_devices": sum(t.get("num_devices", 1) for t in topos),
            "heterogeneous": True,
            "mesh_shapes": {f"replica{i}": t.get("mesh_shape")
                            for i, t in enumerate(topos)},
        }

    def reset_metrics(self) -> None:
        for e in self.replicas:
            e.reset_metrics()
