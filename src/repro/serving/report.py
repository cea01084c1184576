"""Served-throughput accounting: wall-clock + analytic GHOST hardware cost.

The engine records one ``RequestRecord`` per served request; ``ServeReport``
folds them into the numbers a deployment dashboard (or the serving
benchmark's JSON) wants: functional req/s on this host, latency percentiles,
per-model served counts, queue-wait / anti-starvation behavior (max wait in
wall seconds, plus legacy serve-iteration ticks), per-model p99-vs-SLO
attainment for every model carrying an ``slo_ms`` contract,
admission-control outcomes (admitted / rejected / shed), preprocessing-cache
effectiveness, how many jit traces the (model, bucket) executor pool
actually paid, and the accumulated GHOST latency/energy from the analytic
model (photonic/perf.py) — i.e. what the same request stream would cost on
the accelerator.

Durations are measured with ``time.perf_counter()`` (monotonic): wall-clock
time is not, and latency stats must never go negative under a clock step.
SLO deadlines are absolute ``perf_counter`` instants (``t_submit +
slo_ms``), so ``slo_met`` is exactly ``latency_s * 1e3 <= slo_ms``.
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Optional

import jax
import numpy as np


@dataclasses.dataclass
class RequestRecord:
    rid: int
    model_id: str
    num_nodes: int
    num_edges: int
    bucket: str
    cache_hit: bool
    latency_s: float           # monotonic time: submit -> device output
                               # copied back to the host
    batch_size: int            # real requests in the batch that served it
    wait_ticks: int = 0        # serve iterations spent waiting in the queue
    wait_s: float = 0.0        # submit -> batch extracted (node queries:
                               # sampling and prep included)
    hw_latency_s: float = 0.0  # analytic GHOST inference latency
    hw_energy_j: float = 0.0
    # SLO contract (models registered with slo_ms=): 0.0 = no contract.
    slo_ms: float = 0.0
    deadline_s: float = float("inf")  # absolute perf_counter deadline
    slo_met: Optional[bool] = None    # None when the model has no SLO
    # Node-query (neighborhood-sampled) intake path only:
    node_query: bool = False
    num_seeds: int = 0         # query nodes answered by this request
    sample_s: float = 0.0      # host-side k-hop sampling time
    sampled_nodes: int = 0     # real vertices in the sampled subgraph
    sampled_edges: int = 0
    fanouts: str = ""          # e.g. "10x5" ("full" for a None layer)
    devices: tuple = ()        # ids of the devices holding the batch output
    # Stage budget (seconds, perf_counter; see ``Stage``).  Each stage
    # starts where the previous one ended, so wait_s + stack_s +
    # device_wait_s + h2d_s + run_s + publish_s + pickup_s is submit to
    # pickup; prep_s (and sample_s) lie inside wait_s.  The batch stages
    # carry the same value on every record of one batch.
    batch: int = -1            # serve tick of the batch that served it
    prep_s: float = 0.0        # cache lookup or partition, bucket padding
    stack_s: float = 0.0       # batch: extracted -> stacked on the host
    stack_reused: bool = False  # batch: stacked into a reused buffer set
    device_wait_s: float = 0.0  # batch: stacked -> device lock held
    h2d_s: float = 0.0         # batch: device lock -> inputs on the device
    h2d_bytes: int = 0         # batch: bytes of its stack handed to
                               # device_put (the zero fill of a new
                               # device slot set is not counted)
    h2d_slots: int = 0         # batch: slots copied in (the filled ones
                               # where the pool copies by slot, else all)
    run_s: float = 0.0         # batch: executor call -> output on the host
    publish_s: float = 0.0     # batch: readout, records, ordered publish
    # Attention models (GAT) only, 0 elsewhere; batch values, summed over
    # the model's attention layers:
    attn_edges: int = 0        # real attended entries of the batch's
                               # requests: edges that are not loops, plus
                               # one self term per vertex
    attn_entries: int = 0      # tile entries the attention kernel swept:
                               # slots x tiles x V x N, empty slots too
    pickup_s: Optional[float] = None  # published -> result() returned
                                      # (None until the result is taken)


class Stage:
    """Times one serving stage and spans it on the profiler's clock.

    ``with Stage("stack", batch=tick, device=0) as st:`` leaves the
    ``perf_counter`` stamps ``st.t0``/``st.t1`` around the body and, while
    a ``jax.profiler`` trace runs, writes a ``gnn.stack`` host event with
    the keyword metadata over the same interval (``rid`` for request
    stages, ``batch`` and ``device`` for batch stages).  With no trace
    running the annotation costs next to nothing.
    """

    __slots__ = ("_span", "t0", "t1")

    def __init__(self, name: str, **meta):
        self._span = jax.profiler.TraceAnnotation(f"gnn.{name}", **meta)

    def __enter__(self) -> "Stage":
        self._span.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        self._span.__exit__(*exc)


def _percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q)) if values else 0.0


def slo_attainment_from(records: list["RequestRecord"]) -> dict:
    """Per-model (and overall) SLO attainment over served records.

    Only records whose model carries a contract (``slo_ms > 0``) count;
    a catalog without SLOs yields ``{}``.  Per model: the contract, how
    many requests it covered, how many met it, the attainment fraction,
    and the served p99 latency next to the SLO it is measured against —
    the "p99 vs SLO" pairing a latency dashboard plots.  Shed/rejected
    requests never produce records, so attainment here is over *answered*
    requests; the admission counters in the same report complete the
    offered-traffic picture.
    """
    slo_records = [r for r in records if r.slo_ms > 0]
    if not slo_records:
        return {}
    per_model: dict[str, dict] = {}
    by_model: dict[str, list[RequestRecord]] = {}
    for r in slo_records:
        by_model.setdefault(r.model_id, []).append(r)
    for model_id, recs in by_model.items():
        met = sum(1 for r in recs if r.slo_met)
        lats = [r.latency_s for r in recs]
        per_model[model_id] = {
            "slo_ms": recs[0].slo_ms,
            "served": len(recs),
            "met": met,
            "attainment": met / len(recs),
            "p99_latency_ms": _percentile(lats, 99) * 1e3,
            "p99_over_slo": (_percentile(lats, 99) * 1e3 / recs[0].slo_ms
                             if recs[0].slo_ms else 0.0),
        }
    total_met = sum(m["met"] for m in per_model.values())
    total = sum(m["served"] for m in per_model.values())
    return {
        "served": total,
        "met": total_met,
        "attainment": total_met / total,
        "per_model": per_model,
    }


@dataclasses.dataclass
class ServeReport:
    requests: int
    wall_s: float
    req_per_s: float
    p50_latency_ms: float
    p99_latency_ms: float
    mean_batch_size: float
    cache_hits: int
    cache_misses: int
    cache_hit_rate: float
    traces_compiled: int
    buckets: dict            # bucket description -> requests served in it
    per_model: dict          # model_id -> requests served for it
    backend: str
    scheduler: str
    max_wait_ticks: int      # worst queue wait observed in serve iterations
                             # (legacy gauge — iteration rate varies with
                             # load under the always-on loop)
    admitted: int
    rejected: int
    shed: int
    reject_rate: float
    hw_latency_s: float
    hw_energy_j: float
    hw_req_per_s: float
    hw_avg_power_w: float
    max_wait_s: float = 0.0  # worst queue wait in wall seconds — served,
                             # still waiting, or shed (the primary
                             # starvation gauge under the async loop)
    slo_attainment: dict = dataclasses.field(default_factory=dict)
                             # per-model p99-vs-SLO attainment (see
                             # slo_attainment_from); {} = no SLO'd models
    kernel_configs: dict = dataclasses.field(default_factory=dict)
                             # shape-class key -> live kernel config
                             # ({} = hardcoded defaults, no tuner/override)
    topology: dict = dataclasses.field(default_factory=dict)
                             # mesh topology baked into the executor traces
                             # (num_devices / mesh_shape / shard_axis);
                             # {} = single-device, no mesh
    replicas: dict = dataclasses.field(default_factory=dict)
                             # replica name -> per-replica summary (router
                             # reports only; {} for a single engine)
    node_query_stats: dict = dataclasses.field(default_factory=dict)
                             # neighborhood-sampled intake counters ({} when
                             # no node queries were served): queries, seeds,
                             # sample-time percentiles, subgraph sizes,
                             # fanout mix
    unmeetable: int = 0      # subset of `rejected`: refused at enqueue
                             # because the SLO deadline was infeasible per
                             # the learned service-time model
    service_time_ms: dict = dataclasses.field(default_factory=dict)
                             # "model_id/bucket" -> expected batch service
                             # time (ms), the EWMA driving admission /
                             # urgency / router slack ({} = nothing warm)
    pipeline: dict = dataclasses.field(default_factory=dict)
                             # serve-loop pipeline overlap: depth plus
                             # per-stage busy seconds and busy fractions
                             # of wall clock (device execution serializes
                             # behind the engine's device lock, so exec is
                             # occupancy <= ~1.0; overlap shows up as
                             # exec staying near 1.0 while stack-busy is
                             # nonzero — host work hidden behind the device)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2, default=float)

    def pretty(self) -> str:
        return (
            f"served {self.requests} requests in {self.wall_s:.2f}s "
            f"({self.req_per_s:.1f} req/s functional, backend={self.backend}, "
            f"scheduler={self.scheduler})\n"
            f"  latency p50={self.p50_latency_ms:.1f}ms "
            f"p99={self.p99_latency_ms:.1f}ms, "
            f"mean batch {self.mean_batch_size:.1f}, "
            f"max queue wait {self.max_wait_s * 1e3:.1f}ms "
            f"({self.max_wait_ticks} ticks)\n"
            f"  admission: {self.admitted} admitted / {self.rejected} rejected"
            f" ({self.unmeetable} SLO-unmeetable)"
            f" / {self.shed} shed (reject rate {self.reject_rate:.2f})\n"
            + (f"  SLO attainment: {self.slo_attainment['met']}/"
               f"{self.slo_attainment['served']} "
               f"({self.slo_attainment['attainment']:.3f}) — "
               + ", ".join(
                   f"{m}: {v['attainment']:.2f} "
                   f"(p99 {v['p99_latency_ms']:.1f}ms vs SLO "
                   f"{v['slo_ms']:.0f}ms)"
                   for m, v in self.slo_attainment["per_model"].items())
               + "\n" if self.slo_attainment else "")
            + (f"  expected service (EWMA): "
               + ", ".join(f"{k}: {v:.2f}ms"
                           for k, v in sorted(self.service_time_ms.items()))
               + "\n" if self.service_time_ms else "")
            + (f"  pipeline depth {self.pipeline['depth']}: "
               f"exec-busy {self.pipeline.get('exec_busy_frac', 0.0):.0%} / "
               f"stack-busy {self.pipeline.get('stack_busy_frac', 0.0):.0%} "
               f"of wall clock\n" if self.pipeline else "")
            + f"  per model: {self.per_model}\n"
            f"  preprocess cache: {self.cache_hits} hits / "
            f"{self.cache_misses} misses (hit rate {self.cache_hit_rate:.2f})\n"
            f"  jit traces compiled: {self.traces_compiled} "
            f"across buckets {self.buckets}\n"
            + (f"  kernel configs: {self.kernel_configs}\n"
               if self.kernel_configs else "")
            + (f"  mesh: {self.topology.get('num_devices')} devices "
               f"{self.topology.get('mesh_shape')} "
               f"(axis={self.topology.get('shard_axis')}, "
               f"strategy={self.topology.get('strategy')})\n"
               if self.topology else "")
            + (f"  replicas: {self.replicas}\n" if self.replicas else "")
            + (f"  node queries: {self.node_query_stats['queries']} "
               f"({self.node_query_stats['seeds']} seeds, "
               f"fanouts {self.node_query_stats['fanouts']}), "
               f"sample p50={self.node_query_stats['sample_p50_ms']:.1f}ms "
               f"p99={self.node_query_stats['sample_p99_ms']:.1f}ms, "
               f"mean subgraph "
               f"{self.node_query_stats['mean_sampled_nodes']:.0f} nodes / "
               f"{self.node_query_stats['mean_sampled_edges']:.0f} edges\n"
               if self.node_query_stats else "")
            + f"  GHOST hardware estimate: {self.hw_latency_s * 1e6:.1f} us, "
            f"{self.hw_energy_j * 1e3:.3f} mJ, {self.hw_req_per_s:.0f} req/s, "
            f"avg power {self.hw_avg_power_w:.1f} W"
        )


def build_report(
    records: list[RequestRecord],
    wall_s: float,
    cache_stats,
    traces_compiled: int,
    backend: str,
    scheduler: str = "fifo",
    admission_stats=None,
    queue_max_wait_ticks: int = 0,
    queue_max_wait_s: float = 0.0,
    kernel_configs: Optional[dict] = None,
    topology: Optional[dict] = None,
    replicas: Optional[dict] = None,
    service_time_ms: Optional[dict] = None,
    pipeline: Optional[dict] = None,
) -> ServeReport:
    if pipeline:
        # Busy seconds -> fractions of the measured wall clock.  Device
        # execution serializes behind the engine's device lock, so the
        # exec fraction is device occupancy (~<= 1.0); pipelining shows
        # up as exec near 1.0 with stacking/readout hidden behind it.
        pipeline = dict(pipeline)
        for stage in ("stack", "exec"):
            busy = pipeline.get(f"{stage}_busy_s", 0.0)
            pipeline[f"{stage}_busy_frac"] = (busy / wall_s
                                              if wall_s > 0 else 0.0)
    lats = [r.latency_s for r in records]
    buckets: dict[str, int] = {}
    per_model: dict[str, int] = {}
    for r in records:
        buckets[r.bucket] = buckets.get(r.bucket, 0) + 1
        per_model[r.model_id] = per_model.get(r.model_id, 0) + 1
    hw_lat = sum(r.hw_latency_s for r in records)
    hw_e = sum(r.hw_energy_j for r in records)
    nq = [r for r in records if r.node_query]
    node_query_stats: dict = {}
    if nq:
        samples = [r.sample_s for r in nq]
        fanout_mix: dict[str, int] = {}
        for r in nq:
            fanout_mix[r.fanouts] = fanout_mix.get(r.fanouts, 0) + 1
        node_query_stats = {
            "queries": len(nq),
            "seeds": sum(r.num_seeds for r in nq),
            "fanouts": fanout_mix,
            "sample_p50_ms": _percentile(samples, 50) * 1e3,
            "sample_p99_ms": _percentile(samples, 99) * 1e3,
            "mean_sampled_nodes": float(np.mean(
                [r.sampled_nodes for r in nq])),
            "mean_sampled_edges": float(np.mean(
                [r.sampled_edges for r in nq])),
        }
    return ServeReport(
        requests=len(records),
        wall_s=wall_s,
        req_per_s=len(records) / wall_s if wall_s > 0 else 0.0,
        p50_latency_ms=_percentile(lats, 50) * 1e3,
        p99_latency_ms=_percentile(lats, 99) * 1e3,
        mean_batch_size=(float(np.mean([r.batch_size for r in records]))
                         if records else 0.0),
        cache_hits=cache_stats.hits,
        cache_misses=cache_stats.misses,
        cache_hit_rate=cache_stats.hit_rate,
        traces_compiled=traces_compiled,
        buckets=buckets,
        per_model=per_model,
        backend=backend,
        scheduler=scheduler,
        max_wait_ticks=max(
            max((r.wait_ticks for r in records), default=0),
            queue_max_wait_ticks),
        max_wait_s=max(
            max((r.wait_s for r in records), default=0.0),
            queue_max_wait_s),
        slo_attainment=slo_attainment_from(records),
        admitted=admission_stats.admitted if admission_stats else len(records),
        rejected=admission_stats.rejected if admission_stats else 0,
        shed=admission_stats.shed if admission_stats else 0,
        reject_rate=admission_stats.reject_rate if admission_stats else 0.0,
        hw_latency_s=hw_lat,
        hw_energy_j=hw_e,
        hw_req_per_s=len(records) / hw_lat if hw_lat > 0 else 0.0,
        hw_avg_power_w=hw_e / hw_lat if hw_lat > 0 else 0.0,
        kernel_configs=kernel_configs or {},
        topology=topology or {},
        replicas=replicas or {},
        node_query_stats=node_query_stats,
        unmeetable=(getattr(admission_stats, "unmeetable", 0)
                    if admission_stats else 0),
        service_time_ms=service_time_ms or {},
        pipeline=pipeline or {},
    )
