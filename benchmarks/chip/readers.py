"""Shared arithmetic of the per-layer metric readers (metrics/*.py).

Each reader takes the run's context (cell.trace_context: trace events,
the step launches recorded while tracing, the model's sizes, the chip's
peaks, the requests, the window's counters) and returns a number, or
None where the run gave it nothing to read.  A share of a roofline or of
a peak is never returned as 0 for want of data.
"""

from __future__ import annotations

import numpy as np

import trace_reduce as trace_lib
import work as work_lib

DECODE_PROGRAM = "jit_decode_step"
PREFILL_PROGRAM = "jit_prefill_chunk_step"


def step_ms(ctx, program):
    seconds, n = trace_lib.program_seconds(ctx.events, program)
    return seconds / n * 1e3 if n else None


def queue_wait_mean_ms(ctx):
    waits = [(r.admit - r.due) * 1e3 for r in ctx.records
             if r.admit is not None]
    return float(np.mean(waits)) if waits else None


def idle_share_pct(ctx):
    share = trace_lib.idle_share_in_spans(ctx.events, "bench.step")
    return None if share is None else 100.0 * share


def launches_work(ctx, kinds):
    """Model work of the step launches of ``kinds`` made while tracing:
    every token a launch processed, and the head for every row whose
    logits were sampled."""
    total = work_lib.Work()
    for launch in ctx.launches:
        if launch["kind"] not in kinds:
            continue
        idx, vld = launch["index"], launch["valid"]
        n = int(vld.sum())
        # positions idx .. idx + vld - 1 of each row
        pos = float(np.sum(vld * idx + vld * (vld - 1) / 2))
        total = total + work_lib.layers_work(ctx.view, n, pos) \
            + work_lib.head_work(ctx.view, int(launch["last"].sum()))
    return total


def mfu_pct(ctx):
    """Least time at the chip's peaks for the model work of every launch
    made while tracing (integer ops at the int8 peak, float ops at the
    bf16 peak) over the traced window's length."""
    w = launches_work(ctx, ("decode", "prefill"))
    if not ctx.launches or not ctx.traced_s:
        return None
    at_peak = (w.int_ops / ctx.peaks["int8_ops"]
               + w.float_ops / ctx.peaks["bf16_flops"])
    return 100.0 * at_peak / ctx.traced_s
