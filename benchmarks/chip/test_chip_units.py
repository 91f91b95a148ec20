"""CPU tests of the benchmark's parts that need no chip: the traffic
generator, the end-to-end arithmetic, the work counts, the peaks table and
the trace reduction."""

from __future__ import annotations

import math
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import peaks  # noqa: E402
import trace_reduce as tr  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402

CHAT = {"arrival": "poisson", "rate_per_s": 0.8,
        "prompt": {"median": 256, "sigma": 0.7, "min": 32, "max": 1024},
        "output": {"median": 128, "sigma": 0.7, "min": 16, "max": 512}}
BACKLOG = {"arrival": "backlog", "backlog": 40,
           "prompt": {"median": 1536, "sigma": 0.35, "min": 1024,
                      "max": 3072},
           "output": {"median": 64, "sigma": 0.4, "min": 32, "max": 128}}
STABLELM = {"num_layers": 24, "d_model": 2048, "num_heads": 32,
            "num_kv_heads": 32, "d_ff": 5632, "vocab_size": 100352,
            "head_dim": None}


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------

def _key(plan):
    return [(p.uid, p.due_s, p.max_new_tokens, p.prompt.tobytes())
            for p in plan]


@pytest.mark.parametrize("mix", [CHAT, BACKLOG], ids=["chat", "backlog"])
def test_same_seed_same_schedule(mix):
    seed = 2**40 + 12345
    a = traffic.schedule(mix, seed, 51, 100352)
    b = traffic.schedule(mix, seed, 51, 100352)
    c = traffic.schedule(mix, seed + 1, 51, 100352)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


@pytest.mark.parametrize("mix", [CHAT, BACKLOG], ids=["chat", "backlog"])
def test_every_seed_holds_the_same_work(mix):
    """Seeds draw the token ids; the sizes and arrivals are the mix's own,
    so a seed never changes how much work a run holds or when."""
    runs = [traffic.schedule(mix, s, 51, 1000) for s in (1, 2, 3**20)]
    shape = [[(p.due_s, len(p.prompt), p.max_new_tokens) for p in r]
             for r in runs]
    assert shape[0] == shape[1] == shape[2]


@pytest.mark.parametrize("mix", [CHAT, BACKLOG], ids=["chat", "backlog"])
def test_lengths_are_clipped(mix):
    plan = traffic.schedule(mix, 7, 51, 1000)
    p, o = mix["prompt"], mix["output"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in plan)
    assert all(o["min"] <= r.max_new_tokens <= o["max"] for r in plan)
    q = traffic.lognormal_quantiles(p, 4000)
    assert q.min() == p["min"] or q.min() > p["min"]
    assert q.max() <= p["max"]
    clipped = traffic.lognormal_quantiles({**p, "max": p["median"]}, 100)
    assert clipped.max() == p["median"] and (clipped == p["median"]).sum() \
        == 50


def test_poisson_arrivals_fit_the_window():
    plan = traffic.schedule(CHAT, 3, 51, 1000)
    assert len(plan) == int(0.8 * 51)
    due = [p.due_s for p in plan]
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < 51
    assert traffic.schedule(BACKLOG, 3, 51, 1000)[-1].due_s == 0.0


def test_token_ids_inside_the_vocabulary():
    plan = traffic.schedule(CHAT, 5, 51, 49155)
    assert all(p.prompt.dtype == np.int32 for p in plan)
    assert max(int(p.prompt.max()) for p in plan) < 49155


# ---------------------------------------------------------------------------
# the harness loop and its arithmetic
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class FakeEngine:
    """Admits one request per step and emits one token per live request
    per step; a step takes ``dt`` on the fake clock."""

    def __init__(self, clock, dt=0.25):
        from repro.serve.engine import Metrics
        self.clock, self.dt = clock, dt
        self.queue, self.live = [], []
        self.metrics = Metrics()

    def submit(self, req):
        self.queue.append(req)
        return True

    def take_queued(self):
        q, self.queue = self.queue, []
        return q

    def step(self):
        if self.queue:
            r = self.queue.pop(0)
            r.admit_time = self.clock()
            self.live.append(r)
        if not self.live:
            return False
        self.clock.t += self.dt
        for r in self.live:
            r.output.append(7)
        self.live = [r for r in self.live
                     if len(r.output) < r.max_new_tokens]
        return True


class Hooks(harness.Hooks):
    def __init__(self, clock):
        self.clock = clock

    def wait(self, seconds):
        self.clock.t += max(seconds, 1e-3)


def _planned(dues, outs):
    return [traffic.Planned(i, d, np.zeros(4, np.int32), o)
            for i, (d, o) in enumerate(zip(dues, outs))]


def test_drive_stamps_due_times_and_tokens():
    clock = FakeClock()
    eng = FakeEngine(clock)
    run = harness.drive(eng, _planned([0.0, 0.6], [3, 2]), 2.0,
                        backlog=False, hooks=Hooks(clock), clock=clock)
    recs = {r.uid: r for r in run["records"]}
    assert run["t0"] == 100.0
    assert recs[0].due == 100.0 and recs[1].due == pytest.approx(100.6)
    assert recs[0].request.submit_time == recs[0].due
    assert recs[0].tokens == [100.25, 100.5, 100.75]
    # due at 100.6, submitted when the step in flight ends (100.75)
    assert recs[1].admit == pytest.approx(100.75)
    assert recs[1].tokens == [pytest.approx(101.0), pytest.approx(101.25)]
    assert harness.failed(run["records"]) == 0
    assert harness.ttft_ms(run["records"]) == [pytest.approx(250.0),
                                               pytest.approx(400.0)]


def test_backlog_withdraws_what_was_never_admitted():
    clock = FakeClock()
    eng = FakeEngine(clock, dt=0.5)
    run = harness.drive(eng, _planned([0.0] * 6, [2] * 6), 1.0,
                        backlog=True, hooks=Hooks(clock), clock=clock)
    assert run["attempted"] == 2
    assert harness.failed(run["records"]) == 0


def test_end_to_end_arithmetic_on_a_stamp_log():
    recs = []
    for uid, (due, toks) in enumerate([(0.0, [0.5, 0.6, 0.8]),
                                       (0.2, [1.2, 1.3]),
                                       (0.4, [])]):
        r = harness.Record(uid, due, 4, 3 if toks else 2)
        r.tokens = list(toks)
        recs.append(r)
    run = {"t0": 0.0, "end": 1.0, "records": recs}
    e2e = harness.end_to_end(run, 1.0)
    assert e2e["ttft_p50_ms"] == pytest.approx(1000.0)
    gaps = [100.0, 200.0, 100.0]
    assert e2e["itl_mean_ms"] == pytest.approx(np.mean(gaps))
    assert e2e["itl_p99_ms"] == pytest.approx(np.percentile(gaps, 99))
    assert e2e["tok_s"] == pytest.approx(3.0)
    assert harness.failed(recs) == 2
    assert math.isinf(harness.ttft_ms(recs)[2])


def test_sample_holds_the_longest_and_is_seeded():
    recs = []
    for uid in range(20):
        r = harness.Record(uid, 0.0, 10 + uid % 7, 5)
        r.tokens = [1.0] * 5
        recs.append(r)
    a = harness.sample_for_check(recs, 9, 6)
    assert a == harness.sample_for_check(recs, 9, 6)
    assert len(a) == 6 and a[0].prompt_len == 16
    assert harness.sample_for_check(recs, 2**45 + 3, 6) != a


# ---------------------------------------------------------------------------
# work counts and peaks
# ---------------------------------------------------------------------------

def test_packed_matmul_work_by_hand():
    w = work.packed_matmul(16, 2048, 5632, w_bits=2, a_bits=2)
    assert w.int_ops == 2 * 16 * 2048 * 5632
    assert w.bytes == 2048 * 5632 / 4 + 16 * 2048 / 4 + 16 * 5632 * 4
    t, bound = w.min_seconds(peaks.peaks_for("TPU v5 lite"))
    assert bound == "bytes" and t == pytest.approx(w.bytes / 819e9)


def test_decode_attention_work_by_hand():
    w = work.decode_attention([100, 300], heads=32, kv_heads=8, hd=128,
                              kv_bits=4)
    assert w.float_ops == 4 * 400 * 32 * 128
    assert w.bytes == 400 * 8 * (2 * 128 / 2 + 4) + 2 * 32 * 128 * 4


def test_model_work_per_token_at_stablelm_shapes():
    proj = 4 * 2048 * 2048 + 3 * 2048 * 5632     # q k v o, up gate down
    w = work.token_work(STABLELM, context=99)
    assert w.int_ops == 24 * 2 * proj
    assert w.float_ops == 24 * 4 * 100 * 32 * 64 + 2 * 2048 * 100352
    many = work.layers_work(STABLELM, 3, 0 + 1 + 2)
    assert many.int_ops == 3 * 24 * 2 * proj
    assert many.float_ops == 24 * 4 * 32 * 64 * (1 + 2 + 3)


def test_unknown_device_kind_raises():
    assert peaks.peaks_for("TPU v5 lite")["int8_ops"] == 393e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v4")


# ---------------------------------------------------------------------------
# trace reduction on synthetic events
# ---------------------------------------------------------------------------

D0, D1, H = "/device:TPU:0", "/device:TPU:1", tr.HOST_PLANE


def _ev(plane, line, name, s, e):
    return tr.Event(plane, line, name, s, e)


EVENTS = [
    _ev(H, "t", "bench.step", 0, 100),
    _ev(H, "t", "bench.step", 200, 300),
    _ev(H, "t", "bench.wait", 100, 200),
    _ev(D0, tr.MODULES_LINE, "jit_decode_step(12)", 10, 60),
    _ev(D0, tr.MODULES_LINE, "jit_prefill_chunk_step(3)", 210, 290),
    _ev(D0, tr.MODULES_LINE, "jit_decode_step(12)", 120, 140),
    _ev(D0, tr.OPS_LINE, "%attn.4 = f32[8]{0} custom-call(f32[8] %p)",
        10, 30),
    _ev(D0, tr.OPS_LINE, "%fusion.3 = f32[8]{0} fusion(%p)", 25, 60),
    _ev(D0, tr.OPS_LINE, "%attn.9 = f32[8]{0} custom-call(f32[8] %q)",
        120, 140),
    _ev(D0, tr.OPS_LINE, "%ulppack_matmul.1 = s32[8]{0} custom-call()",
        210, 290),
    _ev(D1, tr.OPS_LINE, "fusion.1", 0, 300),
]


def test_busy_union_and_idle_share_in_spans():
    assert tr.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]
    assert tr.busy(EVENTS, D0) == [(10, 60), (120, 140), (210, 290)]
    assert tr.busy_seconds(EVENTS) == pytest.approx((150 + 300) / 2 / 1e9)
    # device 0 busy 50 + 80 of the 200 ns inside bench.step; device 1 all
    share = tr.idle_share_in_spans(EVENTS, "bench.step")
    assert share == pytest.approx(((1 - 130 / 200) + 0.0) / 2)
    assert tr.idle_share_in_spans(EVENTS, "no.such.span") is None


def test_device_time_per_program():
    assert tr.module_name("jit_decode_step(12)") == "jit_decode_step"
    assert tr.program_seconds(EVENTS, "jit_decode_step") == (70e-9, 2)
    assert tr.program_seconds(EVENTS, "jit_prefill_chunk_step") == (80e-9, 1)


def test_kernel_attribution_by_name():
    assert tr.op_base("%ulppack_matmul.169 = s32[16,2048]{1,0} "
                      "custom-call(s16[16,1024] %a)") == "ulppack_matmul"
    assert tr.op_base("%copy-start.2 = (s32[4]) copy-start()") == \
        "copy-start"
    assert tr.op_base("fusion") == "fusion"
    assert tr.op_seconds(EVENTS, "attn") == (40e-9, 2)
    assert tr.op_seconds(EVENTS, "ulppack_matmul") == (80e-9, 1)
    top = tr.top_ops(EVENTS, 2)
    assert top == [["ulppack_matmul", 80e-9], ["attn", 40e-9]]


def test_idle_gaps_are_named_by_host_span():
    gaps = tr.idle_gaps(EVENTS, (0, 300), ("bench.wait", "bench.step"))
    assert gaps[0] == ["bench.wait", pytest.approx(70e-9)]
    assert gaps[1] == ["bench.step", pytest.approx(60e-9)]
    assert sorted(g[1] for g in gaps) == pytest.approx(
        sorted([10e-9, 60e-9, 70e-9, 10e-9]))
