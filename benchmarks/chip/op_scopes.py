"""Device ops of a profiler trace with the scope that emitted each and the
program launch that ran it; the program's own host spans.

``jax.profiler.ProfileData`` gives an op its HLO text but not its event
metadata.  The scope is there: the ``tf_op`` stat of the op's metadata
holds the op's ``jax.named_scope`` path and primitive
(``jit(decode_step)/layer_3/attn/kv_write/scatter:``), or, for a layout
copy of a program argument, the argument's name
(``caches[3]['attn']['k']``).  ``read_metadata`` takes it from the
``.xplane.pb`` itself with a small protobuf wire reader that walks only
the device planes' metadata tables.  An op with neither (a copy the
compiler made on its own) still has its ``shape``.

Ops are attributed to launches by time: an op belongs to the ``XLA
Modules`` event (``jit_decode_step(<program id>)``) that contains its
start, on the same device plane.  Everything here reads the first device
plane, as trace_reduce's per-program numbers do.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from pathlib import Path

import trace_reduce as trace_lib

#: the program's host spans (serve/engine.py), innermost first: an idle gap
#: is named by the first whose spans cover half of it
PROGRAM_SPANS = ("engine.cow", "engine.batch", "engine.logits",
                 "engine.drafted", "engine.sample", "engine.accept",
                 "engine.launch.prefill", "engine.launch.decode",
                 "engine.launch.draft", "engine.launch.verify",
                 "engine.launch.draft_prefill", "engine.draft_prefill",
                 "engine.prefill_pass", "engine.decode_pass",
                 "engine.speculative_pass", "engine.admit", "engine.step")
#: the benchmark's own spans around and between ticks (cell.TraceHooks)
BENCH_SPANS = ("bench.stamp", "bench.wait", "bench.step")
#: the program's Pallas kernels, by their pallas_call ``name=``
KERNELS = ("ulppack_matmul", "quantize_pack", "ulppack_attention_decode",
           "ulppack_conv2d")
COPIES = ("copy", "copy-start", "copy-done")

#: where run.py has the profiler write a traced run
TRACE_ROOT = Path(__file__).resolve().parents[2] / "bench-out" / "trace"


# ---------------------------------------------------------------------------
# The xplane's event metadata (protobuf wire format)
# ---------------------------------------------------------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message; a length-delimited
    value is a memoryview of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = bytes(buf[i:i + 8]), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 5:
            val, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield num, wire, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _map_entry(buf):
    key = value = None
    for num, _, val in _fields(buf):
        if num == 1:
            key = _signed(val)
        elif num == 2:
            value = val
    return key, value


def _stat(buf):
    """(metadata id, value) of an XStat: str, int or ref."""
    mid, value = None, None
    for num, wire, val in _fields(buf):
        if num == 1:
            mid = val
        elif num == 5:
            value = bytes(val).decode("utf-8", "replace")
        elif num in (3, 7):
            value = val
        elif num == 4:
            value = _signed(val)
    return mid, value


def _plane_metadata(buf):
    """(plane name, {metadata id: (name, display name, {stat id: value})},
    {stat id: stat name}) of one XPlane, its lines skipped."""
    name, events, stat_names = "", {}, {}
    for num, _, val in _fields(buf):
        if num == 2:
            name = bytes(val).decode("utf-8", "replace")
        elif num == 4:
            key, em = _map_entry(val)
            ev_name, display, stats = "", "", {}
            for n2, _, v2 in _fields(em):
                if n2 == 2:
                    ev_name = bytes(v2).decode("utf-8", "replace")
                elif n2 == 4:
                    display = bytes(v2).decode("utf-8", "replace")
                elif n2 == 5:
                    sid, sval = _stat(v2)
                    stats[sid] = sval
            events[key] = (ev_name, display, stats)
        elif num == 5:
            key, sm = _map_entry(val)
            for n2, _, v2 in _fields(sm):
                if n2 == 2:
                    stat_names[key] = bytes(v2).decode("utf-8", "replace")
    return name, events, stat_names


@dataclasses.dataclass(frozen=True)
class OpInfo:
    scope: tuple      # named-scope path, jit wrappers dropped; () if none
    operand: str      # the argument an op copies, where tf_op names one
    shape: str        # result shape as compiled, "s32[272,16,256]{...}"


def read_metadata(path: str) -> dict:
    """{(program id, HLO text): OpInfo} of every op on the first device
    plane.  A deduplicated op (the compiler's copy of an identical op in
    another layer) takes the scope of the op it copies."""
    buf = memoryview(Path(path).read_bytes())
    planes = {}
    for num, _, val in _fields(buf):
        if num != 1:
            continue
        for n2, _, v2 in _fields(val):   # the name is read before lines
            if n2 == 2:
                if bytes(v2).decode().startswith(trace_lib.DEVICE_PREFIX):
                    name, events, stat_names = _plane_metadata(val)
                    planes[name] = (events, stat_names)
                break
    if not planes:
        return {}
    events, stat_names = planes[min(planes)]
    sid = {v: k for k, v in stat_names.items()}
    raw = {}
    by_display = {}
    for name, display, stats in events.values():
        pid = stats.get(sid.get("program_id"))
        if isinstance(pid, int):      # unsigned, as the launch names it
            pid &= (1 << 64) - 1
        key = (pid, name)
        raw[key] = (stats.get(sid.get("tf_op")),
                    stats.get(sid.get("deduplicated_name")),
                    stats.get(sid.get("shape_with_layout")) or "")
        by_display[(pid, display)] = key
    out = {}
    for key, (tf_op, dedup, shape) in raw.items():
        if tf_op is None and dedup is not None:
            orig = raw.get(by_display.get((key[0], dedup)))
            tf_op = orig[0] if orig else None
        out[key] = op_info(tf_op, shape)
    return out


def op_info(tf_op, shape: str = "") -> OpInfo:
    """``jit(decode_step)/jit(main)/layer_0/attn/core/pallas_call:`` ->
    scope ``("layer_0", "attn", "core")`` (the primitive dropped);
    ``caches[0]['attn']['v']`` -> that operand, no scope."""
    if not tf_op:
        return OpInfo((), "", shape)
    tf_op = tf_op.rstrip(":")
    parts = tf_op.split("/")
    if len(parts) < 2 or "(" not in parts[0]:
        return OpInfo((), tf_op, shape)
    return OpInfo(tuple(p for p in parts[:-1] if "(" not in p), "", shape)


# ---------------------------------------------------------------------------
# Ops by scope and launch
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Op:
    program: str      # "jit_decode_step"; "" outside every launch
    launch: int       # index into the launches, -1 outside every launch
    base: str         # trace_reduce.op_base of its name
    scope: tuple
    operand: str
    shape: str
    start_ns: int
    end_ns: int


_PROGRAM_ID = re.compile(r"\((\d+)\)$")
_SHAPE = re.compile(r"^%?[^ =]+ = (\S+?)(?:\{|\s)")


def launches(events) -> list:
    """``XLA Modules`` events of the first device plane, by start."""
    planes = trace_lib.device_planes(events)
    if not planes:
        return []
    return sorted((e for e in events if e.plane == planes[0]
                   and e.line == trace_lib.MODULES_LINE),
                  key=lambda e: e.start_ns)


def device_ops(events, meta: dict) -> tuple[list, list]:
    """(ops of the first device plane with their scope and launch, the
    launches).  ``meta`` is read_metadata's table; an op it lacks has no
    scope, and its shape is read from its HLO text."""
    planes = trace_lib.device_planes(events)
    mods = launches(events)
    if not planes:
        return [], mods
    starts = [m.start_ns for m in mods]
    ops = []
    for e in events:
        if e.plane != planes[0] or e.line != trace_lib.OPS_LINE:
            continue
        i = bisect.bisect_right(starts, e.start_ns) - 1
        if i >= 0 and e.start_ns < mods[i].end_ns:
            mod = mods[i].name
            m = _PROGRAM_ID.search(mod)
            pid = int(m.group(1)) if m else None
            program = trace_lib.module_name(mod)
        else:
            i, pid, program = -1, None, ""
        info = meta.get((pid, e.name))
        if info is None:
            s = _SHAPE.match(e.name)
            info = OpInfo((), "", s.group(1) if s else "")
        ops.append(Op(program, i, trace_lib.op_base(e.name), info.scope,
                      info.operand, info.shape, e.start_ns, e.end_ns))
    return ops, mods


def under(op: Op, scope: str) -> bool:
    """Whether ``scope`` (``"kv_write"``, ``"attn/core"``) is a run of
    consecutive components of the op's scope path."""
    want = tuple(scope.split("/"))
    path, k = op.scope, len(want)
    return any(path[i:i + k] == want for i in range(len(path) - k + 1))


def named(op: Op) -> bool:
    """Placed by a named scope, or a Pallas kernel named by its call."""
    return bool(op.scope) or op.base in KERNELS


def attributed(op: Op) -> bool:
    """Named, or a copy of a program argument named by its argument."""
    return named(op) or bool(op.operand)


def _union_ns(ops) -> int:
    return sum(e - s for s, e in trace_lib.union(
        (o.start_ns, o.end_ns) for o in ops))


def per_launch_ms(ops, mods, program: str, select):
    """Device ms per launch of ``program`` of the ops ``select`` admits
    (their time union inside each launch, summed, over the launches);
    None without a launch or without a selected op."""
    n = sum(1 for m in mods if trace_lib.module_name(m.name) == program)
    chosen = collections.defaultdict(list)
    for o in ops:
        if o.program == program and select(o):
            chosen[o.launch].append(o)
    if not n or not chosen:
        return None
    return sum(_union_ns(v) for v in chosen.values()) / n / 1e6


def named_share(ops, program: str, pred=named):
    """Share of the device-busy time inside ``program``'s launches that
    ``pred`` admits (by default: under a named scope or a named kernel);
    None without such a launch."""
    by_launch = collections.defaultdict(list)
    for o in ops:
        if o.program == program:
            by_launch[o.launch].append(o)
    busy = sum(_union_ns(v) for v in by_launch.values())
    if not busy:
        return None
    return sum(_union_ns([o for o in v if pred(o)])
               for v in by_launch.values()) / busy


_INDEX = re.compile(r"\[\d+\]")


def site(op: Op, depth: int = 3) -> str:
    """Where an op sits: its program, then the first ``depth`` components
    of its scope below the layer (``attn/core``), else the argument it
    copies (layer index dropped: ``caches[*]['attn']['k']``), else its
    shape."""
    path = tuple(p for p in op.scope if not p.startswith("layer_"))
    if path:
        where = "/".join(path[:depth])
    elif op.operand:
        where = "operand " + _INDEX.sub("[*]", op.operand, count=1)
    else:
        where = f"shape {op.shape}"
    return f"{op.program or 'no launch'}:{where}"


def seconds_by_site(ops, bases=COPIES, depth: int = 3, n: int = 12):
    """[[site, seconds, share], ...] of the ops named ``bases``, the n
    largest first; the shares are of those ops' whole time."""
    acc = collections.Counter()
    for o in ops:
        if o.base in bases:
            acc[site(o, depth)] += o.end_ns - o.start_ns
    total = sum(acc.values())
    return [[k, v / 1e9, v / total] for k, v in acc.most_common(n)]


# ---------------------------------------------------------------------------
# The traced run's files, for the readers
# ---------------------------------------------------------------------------

def load(path: str) -> tuple:
    """(events with the program's and the benchmark's host spans, device
    ops, launches) of a trace file."""
    events = trace_lib.load(path,
                            host_names=set(PROGRAM_SPANS + BENCH_SPANS))
    ops, mods = device_ops(events, read_metadata(path))
    return events, ops, mods


def _same_launches(a, b) -> bool:
    return [(m.name, m.start_ns) for m in a] == \
        [(m.name, m.start_ns) for m in b]


def traced(ctx):
    """load() of the run the reader context holds, or None; kept on the
    context as ``ctx.scoped``, so the readers of one run read the file
    once.  The trace is ``ctx.xplane`` where the context names it; else
    the newest ``.xplane.pb`` under run.py's trace directory, taken only
    if its launches are the context's own (a trace from another run
    reads nothing)."""
    if not hasattr(ctx, "scoped"):
        ctx.scoped = None
        path = getattr(ctx, "xplane", None)
        if path is not None:
            ctx.scoped = load(path)
        else:
            paths = glob.glob(str(TRACE_ROOT / "**" / "*.xplane.pb"),
                              recursive=True)
            own = launches(ctx.events)
            if paths and own:
                got = load(max(paths, key=os.path.getmtime))
                if _same_launches(got[2], own):
                    ctx.scoped = got
    return ctx.scoped


def device_ms(ctx, program: str, select):
    got = traced(ctx)
    return None if got is None else per_launch_ms(got[1], got[2], program,
                                                  select)


def host_ms_per_step(ctx, span: str):
    """Host time inside the program's ``span`` per ``engine.step``."""
    got = traced(ctx)
    if got is None:
        return None
    events = got[0]
    steps = sum(1 for e in events if e.plane == trace_lib.HOST_PLANE
                and e.name == "engine.step")
    spans = trace_lib.host_spans(events, span)
    if not steps or not spans:
        return None
    return sum(e - s for s, e in spans) / steps / 1e6


# ---------------------------------------------------------------------------
# A traced run's breakdown, for PERF.md
# ---------------------------------------------------------------------------

HALVES = ("embed", "attn/qkv", "attn/kv_write", "attn/core", "attn/out",
          "attn", "mlp", "head")


def half(op: Op) -> str:
    """The first of HALVES the op lies under; else its argument or
    ``unscoped``."""
    for h in HALVES:
        if under(op, h):
            return h
    if op.base in KERNELS:
        return op.base
    return "operand" if op.operand else "unscoped"


def _subtract(a, b) -> list:
    """Merged intervals ``a`` less merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def idle_by_span(events, labels=PROGRAM_SPANS) -> list:
    """[[label, seconds], ...]: the first device's idle time inside the
    host spans' extent, each idle nanosecond given to the first of
    ``labels`` (innermost first) whose spans cover it, the rest to ``no
    host span``; largest first."""
    planes = trace_lib.device_planes(events)
    host = [e for e in events if e.plane == trace_lib.HOST_PLANE]
    if not planes or not host:
        return []
    window = [(min(e.start_ns for e in host), max(e.end_ns for e in host))]
    idle = _subtract(window, trace_lib.busy(events, planes[0]))
    acc = collections.Counter()
    for label in labels:
        spans = trace_lib.host_spans(events, label)
        acc[label] = trace_lib.overlap(idle, spans)
        idle = _subtract(idle, spans)
    acc["no host span"] = sum(e - s for s, e in idle)
    return [[k, v / 1e9] for k, v in acc.most_common() if v]


def breakdown(path: str) -> dict:
    """Per program: launches, device ms per launch by model half, the
    named and attributed shares; the copy ops by site; idle time by the
    innermost program span; host ms per engine.step by span."""
    events, ops, mods = load(path)
    out = {"programs": {}}
    for prog in sorted({o.program for o in ops}):
        n = sum(1 for m in mods if trace_lib.module_name(m.name) == prog)
        halves = collections.defaultdict(list)
        for o in ops:
            if o.program == prog:
                halves[(o.launch, half(o))].append(o)
        by_half = collections.Counter()
        for (_, h), v in halves.items():
            by_half[h] += _union_ns(v) / max(n, 1) / 1e6
        out["programs"][prog] = {
            "launches": n,
            "ms_per_launch": per_launch_ms(ops, mods, prog, lambda o: True),
            "by_half_ms": dict(by_half.most_common()),
            "named_share": named_share(ops, prog),
            "attributed_share": named_share(ops, prog, attributed)}
    out["copies"] = seconds_by_site(ops, n=16)
    out["ops"] = trace_lib.top_ops(events, 12)
    out["idle_by_span"] = idle_by_span(events, PROGRAM_SPANS + BENCH_SPANS)
    steps = sum(1 for e in events if e.plane == trace_lib.HOST_PLANE
                and e.name == "engine.step")
    out["host_ms_per_step"] = {
        name: sum(e - s for s, e in trace_lib.host_spans(events, name))
        / steps / 1e6 for name in PROGRAM_SPANS} if steps else {}
    out["steps"] = steps
    return out


if __name__ == "__main__":
    import json
    import sys

    print(json.dumps(breakdown(trace_lib.find_xplane(sys.argv[1])),
                     indent=1))
