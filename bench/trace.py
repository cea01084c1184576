"""The profiler trace of a window, and its reduction to device time.

A ``--trace 1`` run records a JAX profiler trace over its window and its
drain.  ``reduce`` reads the ``.xplane.pb`` with ``jax.profiler
.ProfileData`` and sums, per device plane (``/device:TPU:<n>``):

- busy time: the union of the intervals in which an operation ran
  (the ``XLA Ops`` line);
- time per operation name and per program name (the ``XLA Modules``
  line);
- the idle gaps between operations, each named by the host event that
  overlaps it most.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import re
import shutil
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# An op event is named by its HLO instruction: "%name = type opcode(...)".
_OPCODE = re.compile(r"[\]})]\s([a-z][a-z0-9_-]*)\(")
_TYPE = re.compile(r"^%\S+ = ([a-z0-9]+\[[0-9,]*\])")
# Ops that contain other ops' events (their time is counted in those).
CONTAINERS = ("while", "call", "conditional", "async-start", "async-done")


def is_kernel(name: str) -> bool:
    """A Pallas kernel's event: the Mosaic custom call, or the fusion XLA
    makes of it, which keeps the ``closed_call`` name of the pallas_call."""
    return ('custom_call_target="tpu_custom_call"' in name
            or name.startswith("%closed_call"))


def op_kind(name: str) -> str:
    """A name for an op event that stays put across compiles: its opcode
    (``pallas`` for a kernel) and its result type without layout."""
    opcode = _OPCODE.search(name)
    kind = "pallas" if is_kernel(name) else (
        opcode.group(1) if opcode else name.split(" ")[0])
    typ = _TYPE.match(name)
    return f"{kind} {typ.group(1)}" if typ else kind


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                      # mean over the devices used
    devices: int
    ops: dict                          # op_kind -> seconds, all devices
    modules: dict                      # program name -> seconds
    module_runs: dict                  # program name -> executions
    idle_gaps: list                    # [(host activity, seconds)], longest
    kernel_s: float                    # Pallas kernels, all devices


def start(log_dir: Path) -> None:
    import jax

    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # Python calls would swamp the host
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def find(log_dir: Path) -> Path:
    paths = sorted(glob.glob(str(log_dir / "**" / "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return Path(paths[-1])


def _union_s(intervals) -> tuple:
    """(total covered seconds, [(start, end)] merged) of ns intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged) / 1e9, merged


def _name_gap(s: int, e: int, host_events) -> str:
    best, best_overlap = "host:none", 0
    for name, hs, he in host_events:
        if he <= s or hs >= e:
            continue
        overlap = min(e, he) - max(s, hs)
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def reduce(path: Path, window_s: float, devices_used: int,
           skip_host: tuple = ()) -> Summary:
    """Reduce one trace file; ``skip_host`` names host events (such as the
    benchmark's own window annotation) that never name a gap."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops = collections.Counter()
    modules = collections.Counter()
    runs = collections.Counter()
    kernel_s = 0.0
    busy = []
    gaps = []
    host_events = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host_events += [(e.name, e.start_ns, e.end_ns)
                                for e in line.events
                                if e.name not in skip_host]
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) >= devices_used:
            continue
        intervals = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for e in line.events:
                    intervals.append((e.start_ns, e.end_ns))
                    if is_kernel(e.name):
                        kernel_s += e.duration_ns / 1e9
                    kind = op_kind(e.name)
                    if kind.split(" ")[0] not in CONTAINERS:
                        ops[kind] += e.duration_ns / 1e9
            elif line.name == MODULES_LINE:
                for e in line.events:
                    modules[e.name] += e.duration_ns / 1e9
                    runs[e.name] += 1
        covered, merged = _union_s(intervals)
        busy.append(covered)
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            gaps.append((e0, s1))
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [(_name_gap(s, e, host_events), (e - s) / 1e9)
            for s, e in gaps[:10]]
    return Summary(window_s=window_s,
                   busy_s=sum(busy) / devices_used if busy else 0.0,
                   devices=len(busy), ops=dict(ops), modules=dict(modules),
                   module_runs=dict(runs), idle_gaps=idle,
                   kernel_s=kernel_s)


def overview(path: Path) -> dict:
    """Planes, lines, event counts and the commonest names with their stat
    keys: what to look at before writing a reader against a trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            events = list(line.events)
            names = collections.Counter(e.name for e in events)
            sample = {}
            for e in events[:200]:
                if e.name not in sample:
                    sample[e.name] = {k: str(v)[:120] for k, v in e.stats}
            lines[line.name] = {"events": len(events),
                                "top": names.most_common(25),
                                "stats": dict(list(sample.items())[:12])}
        out[plane.name] = lines
    return out
