"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time,
per-op device time and idle gaps named by what the host was doing.

The traced window is bounded by the benchmark's own host annotations:
it opens at the first ``bench.issue`` and closes at the end of the last
``bench.wait`` (``jax.profiler.TraceAnnotation`` around each unit's
issue and wait phases, on every rank thread).

On each device plane (``/device:TPU:<n>``):

- busy: the union of the intervals of the events on the "XLA Ops" line,
  clipped to the window;
- programs: the events on the "XLA Modules" line (one per execution of
  a compiled program) with their summed duration;
- per-op time: event durations summed by name;
- idle gaps: the window minus busy, each gap given to the host phase
  that overlaps it most (``outside_phases`` where none does).

Every figure is averaged over the device planes.  A trace with no
device plane reduces to None.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
PHASES = ("bench.issue", "bench.wait")
#: an HLO op event's name: "%name = type{layout} opcode(operands)..."
HLO_OP = re.compile(r"^(%\S+) = (\S+?)(?:\{[^}]*\})? ([\w.-]+)\(")


@dataclass
class TraceSummary:
    devices: int
    window_s: float
    busy_s: float
    program_s: float
    programs: float
    ops: list = field(default_factory=list)    # [(name, s)], largest first
    gaps: list = field(default_factory=list)   # [(phase, s)], largest first


def union(intervals: list) -> list:
    """Merged, sorted [start, end] intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: list, lo: float, hi: float) -> list:
    """The parts of [lo, hi] that no interval of `busy` (merged) covers."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append([t, s])
        t = max(t, e)
    if t < hi:
        out.append([t, hi])
    return out


def overlap(merged: list, starts: list, s: float, e: float) -> float:
    """Length of [s, e] covered by `merged` (sorted, disjoint)."""
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    tot = 0.0
    while i < len(merged) and merged[i][0] < e:
        tot += max(0.0, min(e, merged[i][1]) - max(s, merged[i][0]))
        i += 1
    return tot


def op_name(name: str) -> str:
    """An HLO op event's name without its layout and operands."""
    m = HLO_OP.match(name)
    return f"{m[1]} = {m[2]} {m[3]}" if m else name[:120]


def _events(plane, line_name: str) -> list:
    for line in plane.lines:
        if line.name == line_name:
            return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events]
    return []


def reduce_planes(planes) -> TraceSummary | None:
    """The summary of already-parsed planes (see :func:`reduce_file`)."""
    phase_iv: dict = {p: [] for p in PHASES}
    devices = []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in phase_iv:
                    phase_iv[ev.name].append(
                        [ev.start_ns, ev.start_ns + ev.duration_ns])
    starts = [s for s, _ in phase_iv["bench.issue"]]
    ends = [e for _, e in phase_iv["bench.wait"]]
    if not devices or not starts or not ends:
        return None
    lo, hi = min(starts), max(ends)
    merged = {p: union(iv) for p, iv in phase_iv.items()}
    mstarts = {p: [s for s, _ in m] for p, m in merged.items()}
    busy = prog = nprog = 0.0
    ops: dict = {}
    gap_s: dict = {}
    for plane in devices:
        evs = _events(plane, OPS_LINE)
        busy_iv = union(clip([[s, e] for _, s, e in evs], lo, hi))
        busy += sum(e - s for s, e in busy_iv)
        for name, s, e in evs:
            if e > lo and s < hi:
                key = op_name(name)
                ops[key] = ops.get(key, 0.0) + (min(e, hi) - max(s, lo))
        for _, s, e in _events(plane, MODULES_LINE):
            if e > lo and s < hi:
                prog += min(e, hi) - max(s, lo)
                nprog += 1
        for s, e in gaps(busy_iv, lo, hi):
            cover = {p: overlap(merged[p], mstarts[p], s, e)
                     for p in PHASES}
            best = max(cover, key=cover.get)
            name = best if cover[best] > 0 else "outside_phases"
            gap_s[name] = gap_s.get(name, 0.0) + (e - s)
    n = len(devices)

    def ranked(d: dict) -> list:
        return sorted(((k, v / n / 1e9) for k, v in d.items()),
                      key=lambda kv: -kv[1])

    return TraceSummary(devices=n, window_s=(hi - lo) / 1e9,
                        busy_s=busy / n / 1e9, program_s=prog / n / 1e9,
                        programs=nprog / n, ops=ranked(ops),
                        gaps=ranked(gap_s))


def reduce_file(path: str) -> TraceSummary | None:
    """The summary of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes)
