"""Reduction from a profiler trace (``.xplane.pb``) to numbers.

Reads the file with ``jax.profiler.ProfileData`` only. A device plane is
one whose name starts with ``/device:TPU:``; its ``XLA Ops`` line holds one
event per executed operation and its ``XLA Modules`` line one event per
executed program (named ``jit_<function>(<fingerprint>)``). Busy time is the
union of the op intervals of a device; a program's device time is the sum of
its module events. Event times are nanoseconds from the start of the
profile; ``CLOCK_MARK`` — one host annotation whose wall-clock time the
harness recorded — maps the benchmark's own spans onto that clock.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

CLOCK_MARK = "bench_clock_mark"
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class DeviceTrace:
    name: str
    #: (start ns, end ns, op name) on the ops line, sorted by start
    ops: list[tuple[float, float, str]] = field(default_factory=list)
    #: (start ns, end ns, program name) on the modules line
    modules: list[tuple[float, float, str]] = field(default_factory=list)


@dataclass
class Trace:
    devices: list[DeviceTrace]
    #: profile-clock ns of the CLOCK_MARK annotation, or None
    mark_ns: float | None


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: list[DeviceTrace] = []
    mark = None
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            dev = DeviceTrace(plane.name)
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                rows = sorted(
                    (float(e.start_ns), float(e.start_ns + e.duration_ns),
                     e.name) for e in line.events)
                if line.name == OPS_LINE:
                    dev.ops = rows
                else:
                    dev.modules = rows
            devices.append(dev)
        elif plane.name.startswith("/host:") and mark is None:
            for line in plane.lines:
                for e in line.events:
                    if e.name == CLOCK_MARK:
                        mark = float(e.start_ns)
                        break
                if mark is not None:
                    break
    return Trace(devices, mark)


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted disjoint union of (start, end) intervals."""
    out: list[list[float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def busy_seconds(trace: Trace, lo_ns: float, hi_ns: float) -> float:
    """Seconds in [lo, hi] in which an operation ran on a device, averaged
    over the devices in the trace."""
    if not trace.devices:
        return 0.0
    total = 0.0
    for dev in trace.devices:
        merged = union(clip([(a, b) for a, b, _ in dev.ops], lo_ns, hi_ns))
        total += sum(b - a for a, b in merged)
    return total / len(trace.devices) / 1e9


def program_times(trace: Trace, lo_ns: float, hi_ns: float) -> dict:
    """program name -> (calls, device seconds summed over devices) for the
    module events that start inside [lo, hi]. The ``(fingerprint)`` suffix
    is dropped so a recompile keeps the name."""
    out: dict[str, list[float]] = {}
    for dev in trace.devices:
        for start, end, name in dev.modules:
            if lo_ns <= start < hi_ns:
                row = out.setdefault(name.split("(", 1)[0], [0, 0.0])
                row[0] += 1
                row[1] += (end - start) / 1e9
    return {k: (int(v[0]), v[1]) for k, v in out.items()}


def short_op_name(hlo: str) -> str:
    """``%name opcode[(custom_call_target)]`` of an op's HLO text, which
    the ops line carries whole."""
    lhs, _, rhs = hlo.partition(" = ")
    opcode = re.search(r"\b([a-z][a-z\-]*)\(", rhs)
    target = re.search(r'custom_call_target="([^"]+)"', rhs)
    return " ".join(x for x in (lhs, opcode and opcode.group(1),
                                target and f"[{target.group(1)}]") if x)


def top_ops(trace: Trace, lo_ns: float, hi_ns: float, n: int = 10):
    """[[op name, device seconds]] of the ops that took most time."""
    sums: dict[str, float] = {}
    for dev in trace.devices:
        for start, end, hlo in dev.ops:
            if lo_ns <= start < hi_ns:
                name = short_op_name(hlo)
                sums[name] = sums.get(name, 0.0) + (end - start) / 1e9
    rows = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs] for name, secs in rows]


def idle_by_host_activity(trace: Trace, lo_ns: float, hi_ns: float,
                          spans: list[tuple[str, float, float]],
                          n: int = 10):
    """[[what the host was doing, idle seconds]]: every gap of the first
    device's busy union inside [lo, hi] is given to the span name that
    covers most of it (``spans`` are (name, start, end) on the profile
    clock); among the names that cover at least half of a gap the one with
    the least span time in the whole window wins, as the most specific;
    ``no_span`` where nothing covers any of it."""
    if not trace.devices:
        return []
    merged = union(clip([(a, b) for a, b, _ in trace.devices[0].ops],
                        lo_ns, hi_ns))
    gaps, cursor = [], lo_ns
    for a, b in merged:
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if cursor < hi_ns:
        gaps.append((cursor, hi_ns))
    by_name: dict[str, list[tuple[float, float]]] = {}
    for name, a, b in spans:
        by_name.setdefault(name, []).append((a, b))
    covers = {name: union(iv) for name, iv in by_name.items()}
    width = {name: sum(b - a for a, b in clip(iv, lo_ns, hi_ns))
             for name, iv in covers.items()}
    sums: dict[str, float] = {}
    for ga, gb in gaps:
        cover = {name: sum(b - a for a, b in clip(iv, ga, gb))
                 for name, iv in covers.items()}
        half = [n_ for n_, c in cover.items() if c >= (gb - ga) / 2]
        if half:
            best = min(half, key=lambda n_: width[n_])
        elif any(cover.values()):
            best = max(cover, key=cover.get)
        else:
            best = "no_span"
        sums[best] = sums.get(best, 0.0) + (gb - ga) / 1e9
    rows = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs] for name, secs in rows]
