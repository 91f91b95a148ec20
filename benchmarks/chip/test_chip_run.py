"""A whole run of a cell on the CPU at a small size: set-up, window, the
check against the reference, the metrics; the faults the check has to
catch, and the control it has to fail.  Only the look for a chip is
skipped (run.py makes it; the last tests here show it refuses the CPU)."""

from __future__ import annotations

import copy
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import cell  # noqa: E402
import harness  # noqa: E402
import peaks  # noqa: E402

SEED = 2**35 + 17


class Clock:
    seconds = 0.0
    compiles = 0
    cache_hits = 0


def small_parts(workload: str, kv_heads: int = 4, **sizes) -> dict:
    """The cell's own files, at a size the CPU runs in seconds: every
    width cut (``sizes`` override the cuts), the traffic shortened, the
    rate raised to fill a few seconds."""
    bench = harness.load_benchmark(ROOT)
    parts = cell.read_files(bench, harness.cell_files(bench, workload, ROOT))
    parts = copy.deepcopy(parts)
    v = parts["view"]
    v.update(num_layers=2, d_model=128, num_heads=4, num_kv_heads=kv_heads,
             d_ff=256, vocab_size=512)
    v.update(sizes)
    mix = parts["mix"]
    mix["prompt"].update(min=8, max=80, median=40)
    mix["output"].update(min=4, max=20, median=10)
    mix["rate_per_s"] = 4.0
    parts["limits"]["sample_requests"] = 4
    return parts


def run_small(workload, *, trace=False, trace_dir=None, seconds=3.0,
              kv_heads=4, **sizes):
    parts = small_parts(workload, kv_heads, **sizes)
    bench = harness.load_benchmark(ROOT)
    res = cell.run_cell(bench, None, seed=SEED, seconds=seconds, trace=trace,
                        t_start=time.perf_counter(), clock=Clock(),
                        log=lambda *a: None, trace_dir=trace_dir,
                        parts=parts)
    return res, parts


@pytest.mark.parametrize("kv_heads", [4, 2], ids=["mha", "gqa"])
def test_chat_run_is_correct_and_reports_its_metrics(kv_heads):
    """The served tokens agree with the reference, with one kv head per
    query head and with grouped queries (two per kv head)."""
    res, parts = run_small("stablelm-1.6b.chat", kv_heads=kv_heads)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] == 12
    names = {m["name"] for m in parts["e2e"]}
    assert names == {"ttft_p50_ms", "itl_mean_ms", "itl_p99_ms", "setup_s"}
    assert set(res["metrics"]) == names
    assert list(res)[-1] == "checks"
    gap = res["checks"]["max_gap"]
    assert 0 <= gap["value"] <= gap["limit"]


def test_traced_run_reads_its_layer_metrics(tmp_path, monkeypatch):
    # the CPU has no entry in the peaks table; lend it the chip's so the
    # readers run (the device metrics find no device plane here and are
    # left out, as a reader that finds nothing returns nothing)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    res, parts = run_small("stablelm-1.6b.chat", trace=True,
                           trace_dir=str(tmp_path / "trace"))
    assert res["correct"] is True
    names = {m["name"] for m in parts["per_layer"]}
    assert {"queue_wait_mean_ms.serve", "mfu.serve"} <= set(res["metrics"])
    assert set(res["metrics"]) <= names
    assert "decode_step_ms.serve" not in res["metrics"]
    assert res["metrics"]["queue_wait_mean_ms.serve"]["value"] >= 0
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_an_altered_token_is_caught(monkeypatch):
    """The timed path broken underneath: where a token is produced, every
    fifth one is replaced by the row's least likely token."""
    from repro.serve import speculative
    orig = speculative.sample_token
    calls = {"n": 0}

    def altered(logits_row, sp, rng):
        calls["n"] += 1
        if calls["n"] % 5 == 0:
            return int(np.argmin(np.asarray(logits_row, np.float64)[:512]))
        return orig(logits_row, sp, rng)

    monkeypatch.setattr(speculative, "sample_token", altered)
    res, _ = run_small("stablelm-1.6b.chat")
    assert res["correct"] is False
    gap = res["checks"]["max_gap"]
    assert gap["value"] > gap["limit"]


def test_the_float8_control_fails_the_check():
    """The control: the reference computed one precision below bfloat16
    (float8_e4m3fn) put in the program's place, its first token at each
    position of a sound run's served requests judged by the cell's own
    check and limit.  The check calls it not correct, and its widest gap
    is more than three times the sound run's.  The control's gap grows
    with depth (0.08 logit at 2 layers, 0.45 at 12 on the CPU; about 0.8
    at the cell's 24 on the chip: PERF.md), so this run keeps 12 layers
    at d_model 256, a vocabulary of 4096."""
    captured = {}
    orig = cell.check

    def grab(records, *a, **kw):
        captured["records"] = records
        return orig(records, *a, **kw)

    cell.check = grab
    try:
        res, parts = run_small("stablelm-1.6b.chat", num_layers=12,
                               d_model=256, d_ff=512, vocab_size=4096)
    finally:
        cell.check = orig
    assert res["correct"] is True
    ok, checks = cell.check(captured["records"], parts["view"], parts["mix"],
                            parts["limits"], SEED, lambda *a: None,
                            control_cdt=jnp.float8_e4m3fn)
    assert ok is False
    control = checks["max_gap"]["value"]
    assert control > checks["max_gap"]["limit"]
    assert control > 3 * res["checks"]["max_gap"]["value"]


def _run_command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "stablelm-1.6b.chat", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_the_command_exits_nonzero_without_a_tpu():
    p = _run_command(ROOT)
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_the_command_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_command(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
