"""One run of one cell, given its files already read: set-up, the measured
window, the check against the reference, and the metrics.  run.py adds the
command line and the look for a chip; the tests call ``run_cell`` on the
CPU at small sizes.
"""

from __future__ import annotations

import functools
import gc
import json
import shutil
import time
import types
from pathlib import Path

import numpy as np

import harness
import peaks as peaks_lib
import reference as reference_lib
import trace_reduce as trace_lib
import traffic as traffic_lib
import weights as weights_lib

#: seconds of the window the traced run records (its middle)
TRACE_SECONDS = 12.0
#: a window's requests are followed this long past its close
DRAIN_S = 60.0

#: host spans the traced run writes, innermost first (idle-gap labels)
SPANS = ("engine.sample", "engine.launch.prefill", "engine.launch.decode",
         "engine.prefill_pass", "engine.decode_pass", "engine.admit",
         "bench.stamp", "bench.wait", "bench.step")


def read_files(bench: dict, files: dict) -> dict:
    cell = files["cell"]
    e2e, layer = harness.cell_metrics(bench, cell["name"])
    return {"cell": cell,
            "view": harness.load_view(files["config"]),
            "mix": traffic_lib.load_mix(files["traffic"]),
            "limits": json.loads(Path(files["limits"]).read_text()),
            "e2e": e2e, "per_layer": layer}


def warm_up(engine, view: dict, seed: int):
    """Compile (or load) both step programs: one request longer than a
    prefill chunk, two output tokens."""
    from repro.serve.engine import Request
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 99])
    n = engine.prefill_chunk + 8
    engine.submit(Request(uid=-1, prompt=rng.integers(
        0, view["vocab_size"], n).astype(np.int32), max_new_tokens=2))
    engine.run_to_completion()


class TraceHooks(harness.Hooks):
    """Spans around the benchmark's calls into each engine layer, the
    profiler on for [lo, hi) of the window, and a record of every step
    launch made while it is on (rows, positions) for the work counts."""

    def __init__(self, engine, lo: float, hi: float, trace_dir: str):
        import jax
        self.jax = jax
        self.lo, self.hi, self.dir = lo, hi, trace_dir
        self.on = self.done = False
        self.launches = []
        self.t_on = self.t_off = None
        self._wrap(engine, "_admit", "engine.admit")
        self._wrap(engine, "_prefill_pass", "engine.prefill_pass")
        self._wrap(engine, "_decode_pass", "engine.decode_pass")
        self._wrap(engine, "_emit_token", "engine.sample")
        self._wrap_launch(engine, "_prefill", "prefill")
        self._wrap_launch(engine, "_decode", "decode")

    def _wrap(self, engine, attr, name):
        fn = getattr(engine, attr)
        span = self.jax.profiler.TraceAnnotation

        @functools.wraps(fn)
        def wrapped(*a, **kw):
            with span(name):
                return fn(*a, **kw)
        setattr(engine, attr, wrapped)

    def _wrap_launch(self, engine, attr, kind):
        fn = getattr(engine, attr)
        span = self.jax.profiler.TraceAnnotation
        name = f"engine.launch.{kind}"

        def wrapped(params, caches, batch, index, valid, *rest):
            if self.on:
                idx, vld = np.asarray(index), np.asarray(valid)
                last = np.zeros_like(vld, bool)
                if kind == "prefill":
                    for s, req in enumerate(engine.slot_req):
                        if req is not None and vld[s]:
                            last[s] = idx[s] + vld[s] == len(req.prompt)
                else:
                    last = vld > 0
                self.launches.append({"kind": kind, "index": idx,
                                      "valid": vld, "last": last})
            with span(name):
                return fn(params, caches, batch, index, valid, *rest)
        setattr(engine, attr, wrapped)

    def tick(self, now, t0):
        if not self.on and not self.done and now >= t0 + self.lo:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.jax.profiler.start_trace(self.dir)
            self.on, self.t_on = True, time.perf_counter()
        elif self.on and now >= t0 + self.hi:
            self.stop()

    def stop(self):
        if self.on:
            self.t_off = time.perf_counter()
            self.jax.profiler.stop_trace()
            self.on, self.done = False, True

    def step(self, engine):
        with self.jax.profiler.TraceAnnotation("bench.step"):
            return engine.step()

    def wait(self, seconds):
        with self.jax.profiler.TraceAnnotation("bench.wait"):
            time.sleep(seconds)


def check(records, view, mix, limits, seed, log, control_cdt=None):
    """The comparison that decides ``correct``: a sample of the finished
    requests, teacher-forced through the reference; the number compared
    is the widest gap by which a served token's reference logit lies
    below the reference's best at that position.

    With ``control_cdt`` the control stands in the program's place: the
    reference computed at that dtype, whose first token at each of the
    same positions is judged as a served token, by the same limits."""
    n = limits["sample_requests"]
    sample = harness.sample_for_check(records, seed, n)
    length = traffic_lib.longest_request(mix)
    tokens = np.zeros((n, length), np.int32)
    served = np.full((n, length), -1, np.int32)
    if sample:
        t, s = harness.teacher_forcing(sample, length)
        tokens[:len(sample)], served[:len(sample)] = t, s
    w = weights_lib.make_weights(view, seed)
    out = reference_lib.readings(w, view, tokens, served,
                                 control_cdt=control_cdt)
    del w
    at = out["at_served"] if control_cdt is None else out["control_at"]
    mask = served >= 0
    gaps = (out["best"] - at)[mask]
    max_gap = float(gaps.max()) if gaps.size else float("inf")
    who = "program" if control_cdt is None else \
        f"control ({np.dtype(control_cdt).name})"
    log(f"check of the {who}: {len(sample)} requests, {int(mask.sum())} "
        f"served positions against the reference; gap max {max_gap} mean "
        f"{float(gaps.mean()) if gaps.size else float('nan')}, tokens not "
        f"the reference's first {int((gaps > 0).sum())}")
    checks = {"max_gap": {"value": max_gap,
                          "limit": limits["limits"]["max_gap"]}}
    ok = bool(sample) and all(c["value"] <= c["limit"]
                              for c in checks.values())
    return ok, checks


def trace_context(hooks, events, run, view, kind, seconds, records):
    return types.SimpleNamespace(
        events=events, launches=hooks.launches, view=view,
        peaks=peaks_lib.peaks_for(kind), run=run, records=records,
        seconds=seconds, traced_s=hooks.t_off - hooks.t_on)


def run_cell(bench, files, *, seed, seconds, trace, t_start, clock, log,
             trace_dir, parts=None):
    """Set-up, window, check and metrics of one run; returns the result
    object without ``device``'s platform fields.  ``parts`` (tests) stands
    in for the files: read_files' dict."""
    import jax
    from repro.serve.engine import Metrics, ServingEngine

    parts = parts or read_files(bench, files)
    view, mix, limits = parts["view"], parts["mix"], parts["limits"]
    cfg = harness.program_config(view)
    econf = harness.engine_config(view, mix)

    t = time.perf_counter()
    w = jax.block_until_ready(weights_lib.make_weights(view, seed))
    log(f"weights from seed {seed} in {time.perf_counter() - t:.2f}s")
    t = time.perf_counter()
    engine = ServingEngine(cfg, w, config=econf)
    jax.block_until_ready(engine.params)
    del w
    gc.collect()
    log(f"engine built in {time.perf_counter() - t:.2f}s: max_batch "
        f"{engine.max_batch}, pages {engine.num_pages}x{engine.page_size}, "
        f"max_len {engine.max_len}")
    t, c0 = time.perf_counter(), clock.seconds
    warm_up(engine, view, seed)
    log(f"warm-up {time.perf_counter() - t:.2f}s (compile "
        f"{clock.seconds - c0:.2f}s, {clock.compiles} compiles, "
        f"{clock.cache_hits} persistent-cache hits so far)")
    engine.metrics = Metrics()
    planned = traffic_lib.schedule(mix, seed, seconds, view["vocab_size"])
    hooks = None
    if trace:
        span = min(TRACE_SECONDS, seconds)
        lo = (seconds - span) / 2
        hooks = TraceHooks(engine, lo, lo + span, trace_dir)
    compiles0 = clock.compiles
    run = harness.drive(engine, planned, seconds,
                        backlog=mix["arrival"] == "backlog", drain_s=DRAIN_S,
                        hooks=hooks)
    if hooks is not None:
        hooks.stop()
    setup_s = run["t0"] - t_start
    in_window = clock.compiles - compiles0
    if in_window:
        log(f"warning: {in_window} compiles inside the window or drain")
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    records = run["records"]
    failed = harness.failed(records)
    log(f"window {seconds}s: {len(planned)} planned, {run['attempted']} "
        f"attempted, {failed} failed, setup {setup_s:.3f}s, peak HBM {peak}")
    e2e_values = harness.end_to_end(run, seconds)
    e2e_values["setup_s"] = setup_s
    c_end, c_start = run["counters_end"], run["counters_start"]
    counters = {k: getattr(c_end, k) - getattr(c_start, k)
                for k in ("steps", "slot_steps_live", "slot_steps_total",
                          "prefill_tokens", "generated_tokens",
                          "decode_tokens", "admitted")}
    log(f"window counters {json.dumps(counters)}")
    kind = dev.device_kind
    del engine
    gc.collect()

    correct, checks = check(records, view, mix, limits, seed, log)
    result = {"correct": correct, "attempted": run["attempted"],
              "failed": failed, "metrics": {}, "device": {
                  "memory_peak_bytes": peak}}
    if not trace:
        for m in parts["e2e"]:
            if m["name"] in e2e_values:
                result["metrics"][m["name"]] = {
                    "value": e2e_values[m["name"]], "unit": m["unit"]}
        result["checks"] = checks
        return result

    xplane = trace_lib.find_xplane(trace_dir)
    t = time.perf_counter()
    events = trace_lib.load(xplane, host_names=set(SPANS))
    log(f"trace {xplane}: {len(events)} events read in "
        f"{time.perf_counter() - t:.1f}s")
    for name, sec in trace_lib.top_ops(events, 40):
        log(f"device op {name} {sec}")
    ctx = trace_context(hooks, events, run, view, kind, seconds, records)
    ctx.counters = counters
    for m in parts["per_layer"]:
        value = harness.load_reader(m["name"])(ctx)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value,
                                            "unit": m["unit"]}
    result["device"]["busy_s"] = trace_lib.busy_seconds(events)
    result["device"]["window_s"] = ctx.traced_s
    host = [e for e in events if e.plane == trace_lib.HOST_PLANE]
    window = (min(e.start_ns for e in host), max(e.end_ns for e in host)) \
        if host else (0, 0)
    result["breakdown"] = {
        "device_ops": trace_lib.top_ops(events),
        "idle_gaps": trace_lib.idle_gaps(events, window, SPANS)}
    result["checks"] = checks
    return result
