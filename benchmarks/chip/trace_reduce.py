"""Reduction of a JAX profiler trace to the numbers the metrics read.

``load`` turns an ``.xplane.pb`` into flat events; everything else works
on those events, so it can be checked on a small recorded trace without a
chip.  An event is ``(plane, line, name, start_ns, end_ns)``.

Device planes are ``/device:TPU:<n>``.  Their ``XLA Ops`` line holds one
event per operation the device ran, named by its HLO instruction
(``%ulppack_matmul.169 = s32[16,2048]{...} custom-call(...)``: a Pallas
kernel is a custom call named after the function that launched it; see
``op_base``), their ``XLA Modules`` line one event per program launch
(``jit_<function>(<id>)``).  Host spans (``jax.profiler.TraceAnnotation``)
are events on the ``/host:CPU`` plane, on the same clock.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: int
    end_ns: int

    @property
    def dur_ns(self) -> int:
        return self.end_ns - self.start_ns


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str, host_names=None) -> list[Event]:
    """Flat events of the device planes, and of the host plane those whose
    name is in ``host_names`` (the benchmark's own spans; the host plane
    also holds every runtime call, which nothing here reads)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        is_dev = plane.name.startswith(DEVICE_PREFIX)
        if not is_dev and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if is_dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not is_dev and (host_names is None
                                   or ev.name not in host_names):
                    continue
                out.append(Event(plane.name, line.name, ev.name,
                                 int(ev.start_ns), int(ev.end_ns)))
    return out


def device_planes(events) -> list[str]:
    return sorted({e.plane for e in events
                   if e.plane.startswith(DEVICE_PREFIX)})


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def overlap(a, b) -> int:
    """Total length of the intersection of two merged interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def busy(events, plane: str) -> list[tuple[int, int]]:
    """Intervals in which an operation ran on one device."""
    return union((e.start_ns, e.end_ns) for e in events
                 if e.plane == plane and e.line == OPS_LINE)


def busy_seconds(events) -> float:
    """Busy time averaged over the device planes."""
    planes = device_planes(events)
    if not planes:
        return 0.0
    return sum(sum(e - s for s, e in busy(events, p))
               for p in planes) / len(planes) / 1e9


def host_spans(events, name: str) -> list[tuple[int, int]]:
    return union((e.start_ns, e.end_ns) for e in events
                 if e.plane == HOST_PLANE and e.name == name)


def idle_share_in_spans(events, span_name: str):
    """1 - (device-busy time inside the host spans) / (the spans' length),
    averaged over devices; None when there is no span."""
    spans = host_spans(events, span_name)
    total = sum(e - s for s, e in spans)
    planes = device_planes(events)
    if not total or not planes:
        return None
    shares = [1.0 - overlap(busy(events, p), spans) / total for p in planes]
    return sum(shares) / len(shares)


_MODULE_RE = re.compile(r"^(.*?)(\(\d+\))?$")


def module_name(name: str) -> str:
    """``jit_decode_step(123)`` -> ``jit_decode_step``."""
    return _MODULE_RE.match(name).group(1)


def program_launches(events, program: str) -> list[Event]:
    """Launch events of one jitted program, on the first device plane."""
    planes = device_planes(events)
    if not planes:
        return []
    return [e for e in events if e.plane == planes[0]
            and e.line == MODULES_LINE and module_name(e.name) == program]


def program_seconds(events, program: str):
    """(device seconds of all launches, launch count) of one program on
    the first device plane."""
    evs = program_launches(events, program)
    return sum(e.dur_ns for e in evs) / 1e9, len(evs)


_OP_RE = re.compile(r"^%?([^ =]+?)(\.\d+)?(?: =.*)?$", re.S)


def op_base(name: str) -> str:
    """An operation's name without its HLO text and instance number:
    ``%ulppack_matmul.169 = s32[...] custom-call(...)`` ->
    ``ulppack_matmul``; ``%fusion.13 = ...`` -> ``fusion``."""
    m = _OP_RE.match(name)
    return m.group(1) if m else name


def op_seconds(events, base: str) -> tuple[float, int]:
    """(device seconds, count) of the operations named ``base``
    (``op_base``), on the first device plane."""
    planes = device_planes(events)
    if not planes:
        return 0.0, 0
    evs = [e for e in events if e.plane == planes[0]
           and e.line == OPS_LINE and op_base(e.name) == base]
    return sum(e.dur_ns for e in evs) / 1e9, len(evs)


def top_ops(events, n: int = 10) -> list[list]:
    """The n operations (by ``op_base`` name) that took most device time,
    first device plane: [[name, seconds], ...]."""
    planes = device_planes(events)
    if not planes:
        return []
    acc = collections.Counter()
    for e in events:
        if e.plane == planes[0] and e.line == OPS_LINE:
            acc[op_base(e.name)] += e.dur_ns
    return [[k, v / 1e9] for k, v in acc.most_common(n)]


def idle_gaps(events, window: tuple[int, int], labels, n: int = 10):
    """The n longest idle gaps of the first device inside ``window``, each
    named by what the host was doing: the first of ``labels`` (innermost
    span first) whose spans cover half the gap, else the one covering
    most of it.  [[label, seconds], ...]."""
    planes = device_planes(events)
    if not planes:
        return []
    lo, hi = window
    gaps, cur = [], lo
    for s, e in busy(events, planes[0]):
        if s > cur:
            gaps.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        gaps.append((cur, hi))
    gaps = sorted((g for g in gaps if g[1] > g[0]),
                  key=lambda g: g[0] - g[1])[:n]
    spans = {name: host_spans(events, name) for name in labels}
    out = []
    for g in gaps:
        best, best_ns = "no host span", 0
        for name, iv in spans.items():
            ns = overlap([g], iv)
            if 2 * ns >= g[1] - g[0]:
                best = name
                break
            if ns > best_ns:
                best, best_ns = name, ns
        out.append([best, (g[1] - g[0]) / 1e9])
    return out
