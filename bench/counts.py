"""The work a request needs, counted from its real graph at the model's
published widths.  Padding, bucket rounding and tile waste never count.

For each layer (f_in -> f_out) on a graph of V vertices and E edges:

- combine: 2 * V * f_in * f_out FLOPs per weight matrix the layer applies
  to every vertex;
- aggregation: 2 * E * F FLOPs with F = min(f_in, f_out), the narrower
  side, since aggregation and combine commute; it reads 4 bytes per edge
  (its weight or index) and the F-wide float32 rows of V sources, and
  writes those of V destinations.

So the count is the same whichever kernel, order or tile shape does the
work, and a share of a peak computed from it cannot pass 100% unless the
time measured leaves out part of the work.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def model_flops(dims, combines_per_layer: int, vertices: int,
                edges: int) -> float:
    return float(sum(2 * edges * min(fi, fo)
                     + combines_per_layer * 2 * vertices * fi * fo
                     for fi, fo in dims))


def aggregation_work(dims, vertices: int, edges: int) -> tuple:
    """(FLOPs, bytes) of every layer's aggregation."""
    flops = nbytes = 0
    for fi, fo in dims:
        f = min(fi, fo)
        flops += 2 * edges * f
        nbytes += 4 * edges + 2 * 4 * vertices * f
    return float(flops), float(nbytes)


def least_time_s(flops: float, nbytes: float, peak: dict) -> tuple:
    """(seconds, bound): the larger of compute time at the bf16 peak and
    memory time at HBM bandwidth, and which of the two it is."""
    compute = flops / peak["bf16_flops_per_s"]
    memory = nbytes / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
