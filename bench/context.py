"""What a run hands its per-layer metric readers.

Each reader in ``bench/metrics/<name>.py`` has ``read(ctx)``, which returns
the metric's value, or None where this run has nothing to read it from
(then the metric is left out of the result line).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from bench import counts, loops


@dataclasses.dataclass
class Context:
    outcomes: list            # loops.Outcome of every request of the window
    slots: int
    replica_records: list     # per replica: RequestRecord of the window
    stack_busy_s: float
    exec_busy_s: float
    cache_hits: int
    cache_misses: int
    work: list                # (vertices, edges) per served request
    dims: list                # [(f_in, f_out)] per layer
    combines_per_layer: int
    peak: dict
    trace: Optional[object] = None   # trace.Summary of a --trace 1 run

    @property
    def records(self) -> list:
        return [r for recs in self.replica_records for r in recs]

    @property
    def batches(self) -> float:
        return sum(1.0 / r.batch_size for r in self.records)

    # --- host-side spans and counters -------------------------------------

    def lag_p95_ms(self):
        lags = loops.lags_s(self.outcomes)
        return loops.percentile(lags, 95) * 1e3 if lags else None

    def sample_p50_ms(self):
        spans = [r.sample_s for r in self.records if r.node_query]
        return loops.percentile(spans, 50) * 1e3 if spans else None

    def queue_wait_p95_ms(self):
        spans = [r.wait_s for r in self.records]
        return loops.percentile(spans, 95) * 1e3 if spans else None

    def cache_hit_pct(self):
        total = self.cache_hits + self.cache_misses
        return 100.0 * self.cache_hits / total if total else None

    def router_skew(self):
        served = [len(recs) for recs in self.replica_records]
        if len(served) < 2 or not sum(served):
            return None
        return max(served) / (sum(served) / len(served))

    def batch_fill_pct(self):
        b = self.batches
        return 100.0 * len(self.records) / b / self.slots if b else None

    def stack_ms(self):
        b = self.batches
        return 1e3 * self.stack_busy_s / b if b else None

    def exec_ms(self):
        b = self.batches
        return 1e3 * self.exec_busy_s / b if b else None

    # --- device time from the trace ---------------------------------------

    def _program_s(self) -> float:
        return sum(self.trace.modules.values()) if self.trace else 0.0

    def _kernel_s(self) -> float:
        """Device time of the Pallas kernels: on the paths served, the
        aggregation kernels (``kernels/block_spmm.py``,
        ``kernels/fused_block_spmm.py``) are the only ones."""
        return self.trace.kernel_s if self.trace else 0.0

    def mfu_pct(self):
        """Model FLOPs of the requests served / (device time of the
        programs that served them x bf16 peak)."""
        program_s = self._program_s()
        if program_s <= 0 or not self.work:
            return None
        flops = sum(counts.model_flops(self.dims, self.combines_per_layer,
                                       v, e) for v, e in self.work)
        return 100.0 * flops / (program_s * self.peak["bf16_flops_per_s"])

    def agg_roofline(self):
        """(share %, bound) of the aggregation kernels' device time that
        the aggregation work needs at the chip's peaks."""
        kernel_s = self._kernel_s()
        if kernel_s <= 0 or not self.work:
            return None
        flops = nbytes = 0.0
        for v, e in self.work:
            f, b = counts.aggregation_work(self.dims, v, e)
            flops += f
            nbytes += b
        least, bound = counts.least_time_s(flops, nbytes, self.peak)
        return 100.0 * least / kernel_s, bound
