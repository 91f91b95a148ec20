"""The parts of a run that do not need a chip: finding a cell's files by
name, building the program's configuration from a configuration file,
driving an engine through a schedule, and the end-to-end arithmetic.

A cell names a configuration and a traffic mix; each is a file found by
its name:

    configs/<config>.json     sizes as run, source, what was changed
    traffic/<traffic>.json    the mix's parameters (traffic.py)
    limits/<workload>.json    the correctness limits and sample size
    metrics/<metric>.py       one per-layer metric reader: read(ctx)

so a later cell, configuration, mix or metric is new files and entries,
never an edit.
"""

from __future__ import annotations

import collections
import dataclasses
import importlib.util
import json
import math
import statistics
import time
from pathlib import Path

import numpy as np

import traffic as traffic_lib

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


# ---------------------------------------------------------------------------
# Files
# ---------------------------------------------------------------------------

def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def find_cell(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json; cells: "
                   f"{[c['name'] for c in bench['workloads']]}")


def config_entry(bench: dict, name: str) -> dict:
    for cfg in bench["configs"]:
        if cfg["name"] == name:
            return cfg
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def cell_files(bench: dict, workload: str, root: Path = ROOT) -> dict:
    """Paths of everything one cell reads, each checked to exist."""
    cell = find_cell(bench, workload)
    config = config_entry(bench, cell["config"])
    files = {"config": Path(root) / config["file"],
             "traffic": HERE / "traffic" / f"{cell['traffic']}.json",
             "limits": HERE / "limits" / f"{workload}.json"}
    for kind, path in files.items():
        if not path.is_file():
            raise FileNotFoundError(f"{workload}: no {kind} file {path}")
    return {"cell": cell, **files}


def cell_metrics(bench: dict, workload: str) -> tuple[list, list]:
    """(end-to-end entries, per-layer entries) this cell reports: those
    that list it under ``workloads``, or have no such key (per-layer: and
    move an end-to-end metric the cell reports)."""
    def applies(entry):
        return "workloads" not in entry or workload in entry["workloads"]
    e2e = [m for m in bench["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if applies(m) and ("workloads" in m or m["moves"] in names)]
    return e2e, layer


def load_reader(name: str):
    """metrics/<name>.py's ``read`` function."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chip_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# Configuration file -> the model's sizes
# ---------------------------------------------------------------------------

def model_view(raw: dict) -> dict:
    """The sizes the benchmark, the reference and the work counts use,
    from a configuration file (Hugging Face key names)."""
    q = raw["quant"]
    return {
        "name": raw["name"],
        "num_layers": raw["num_hidden_layers"],
        "d_model": raw["hidden_size"],
        "num_heads": raw["num_attention_heads"],
        "num_kv_heads": raw["num_key_value_heads"],
        "d_ff": raw["intermediate_size"],
        "vocab_size": raw["vocab_size"],
        "head_dim": raw.get("head_dim"),
        "tie_embeddings": bool(raw["tie_word_embeddings"]),
        "norm_eps": float(raw.get("rms_norm_eps",
                                  raw.get("layer_norm_eps", 1e-5))),
        "rope_theta": float(raw["rope_theta"]),
        "w_bits": q["w_bits"], "a_bits": q["a_bits"],
        "kv_bits": q["kv_bits"], "lane_dtype": q["lane_dtype"],
        "n_pack": q["n_pack"],
        "engine": raw["engine"],
        "init": raw["init"],
    }


def load_view(path: Path) -> dict:
    return model_view(json.loads(Path(path).read_text()))


def program_config(view: dict):
    """The program's ModelConfig for a view (imports the program)."""
    from repro.configs.base import ModelConfig
    from repro.core.quant import QuantConfig
    quant = QuantConfig(enabled=True, w_bits=view["w_bits"],
                        a_bits=view["a_bits"], kv_bits=view["kv_bits"],
                        lane_dtype=view["lane_dtype"],
                        n_pack=view["n_pack"])
    return ModelConfig(
        name=view["name"], family="dense", num_layers=view["num_layers"],
        d_model=view["d_model"], num_heads=view["num_heads"],
        num_kv_heads=view["num_kv_heads"], d_ff=view["d_ff"],
        vocab_size=view["vocab_size"], head_dim=view["head_dim"],
        tie_embeddings=view["tie_embeddings"], norm_eps=view["norm_eps"],
        rope_theta=view["rope_theta"], param_dtype="bfloat16",
        compute_dtype="bfloat16", quant=quant)


def engine_config(view: dict, mix: dict):
    """The configuration's engine settings; each slot holds the mix's
    longest request, rounded up to whole pages."""
    from repro.serve.config import EngineConfig
    e = view["engine"]
    ps = e["page_size"]
    return EngineConfig(max_batch=e["max_batch"],
                        max_len=-(-traffic_lib.longest_request(mix) // ps)
                        * ps, packed=True, paged=True, page_size=ps,
                        prefill_chunk=e["prefill_chunk"])


# ---------------------------------------------------------------------------
# Driving the engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Record:
    """One request as the benchmark saw it.  Times are perf_counter
    seconds; ``tokens`` holds the time each output token was seen."""
    uid: int
    due: float
    prompt_len: int
    max_new_tokens: int
    request: object = None
    admit: float | None = None
    tokens: list = dataclasses.field(default_factory=list)
    rejected: bool = False

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.max_new_tokens


class Hooks:
    """Where a traced run plugs in: around each step, and to start or
    stop the profiler between steps.  The plain run uses these no-ops."""

    def step(self, engine):
        return engine.step()

    def tick(self, now: float, t0: float):
        pass

    def wait(self, seconds: float):
        time.sleep(seconds)


def drive(engine, planned, seconds: float, *, backlog: bool,
          drain_s: float = 60.0, hooks: Hooks | None = None,
          clock=time.perf_counter, request_cls=None) -> dict:
    """Serve ``planned`` (traffic.Planned, by due time) for ``seconds``.

    Each request is submitted at its due time with ``submit_time`` set to
    it; after every step each live request's new output tokens are
    stamped with the step's end.  When the window closes, requests not
    yet admitted are withdrawn in a backlog mix (they were never
    attempted), and every attempted request is followed to completion
    for at most ``drain_s``.  Returns {"t0", "end", "records",
    "attempted", "counters_start", "counters_end"}."""
    if request_cls is None:
        from repro.serve.engine import Request as request_cls
    hooks = hooks or Hooks()
    pending = collections.deque(planned)
    records: dict[int, Record] = {}
    live: dict[int, Record] = {}
    t0 = clock()
    end = t0 + seconds
    counters_start = dataclasses.replace(engine.metrics)

    def stamp(now):
        for uid in list(live):
            rec = live[uid]
            new = len(rec.request.output) - len(rec.tokens)
            if new > 0:
                rec.tokens.extend([now] * new)
            if rec.admit is None and rec.request.admit_time:
                rec.admit = rec.request.admit_time
            if rec.done:
                del live[uid]

    def submit_due(now):
        while pending and t0 + pending[0].due_s <= now:
            p = pending.popleft()
            due = t0 + p.due_s
            req = request_cls(uid=p.uid, prompt=p.prompt,
                              max_new_tokens=p.max_new_tokens,
                              submit_time=due)
            rec = Record(p.uid, due, len(p.prompt), p.max_new_tokens, req)
            records[p.uid] = rec
            if engine.submit(req):
                live[p.uid] = rec
            else:
                rec.rejected = True

    while True:
        now = clock()
        hooks.tick(now, t0)
        if now >= end:
            break
        submit_due(now)
        if live:
            hooks.step(engine)
            stamp(clock())
        elif pending:
            hooks.wait(max(0.0, min(t0 + pending[0].due_s, end) - clock()))
        else:
            hooks.wait(max(0.0, end - clock()))
    counters_end = dataclasses.replace(engine.metrics)
    if backlog:
        withdrawn = {r.uid for r in engine.take_queued()}
        for uid in withdrawn:
            live.pop(uid, None)
            records.pop(uid, None)
    deadline = clock() + drain_s
    while live and clock() < deadline:
        if not engine.step():
            break
        stamp(clock())
    return {"t0": t0, "end": end, "records": list(records.values()),
            "attempted": len(records), "counters_start": counters_start,
            "counters_end": counters_end}


# ---------------------------------------------------------------------------
# End-to-end arithmetic
# ---------------------------------------------------------------------------

def failed(records) -> int:
    return sum(1 for r in records if r.rejected or not r.done)


def ttft_ms(records) -> list[float]:
    """Due time to first token, per request; a request with no token
    reads infinite."""
    return [(r.tokens[0] - r.due) * 1e3 if r.tokens else math.inf
            for r in records]


def gaps_ms(records) -> list[float]:
    out = []
    for r in records:
        t = r.tokens
        out.extend((b - a) * 1e3 for a, b in zip(t, t[1:]))
    return out


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100), linear between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(run: dict, seconds: float) -> dict:
    """Every end-to-end number a run can report, by metric name."""
    recs = run["records"]
    out = {}
    ttft = ttft_ms(recs)
    if ttft:
        out["ttft_p50_ms"] = float(statistics.median(ttft))
    gaps = gaps_ms(recs)
    if gaps:
        out["itl_mean_ms"] = float(sum(gaps) / len(gaps))
        out["itl_p99_ms"] = percentile(gaps, 99)
    emitted = sum(1 for r in recs for t in r.tokens
                  if run["t0"] <= t <= run["end"])
    out["tok_s"] = emitted / seconds
    return out


def sample_for_check(records, seed: int, n: int) -> list:
    """Up to ``n`` finished requests drawn from ``seed``, the longest
    (prompt plus output) among them."""
    done = sorted((r for r in records if r.done and not r.rejected),
                  key=lambda r: r.uid)
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + r.max_new_tokens,
                                       -r.uid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                 (int(seed) >> 32) & 0xFFFFFFFF, 7])
    pick = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def teacher_forcing(sample, length: int):
    """Token rows [n, length] (prompt, then every served token but the
    last) and the served token at each position (-1 where none)."""
    n = len(sample)
    tokens = np.zeros((n, length), np.int32)
    served = np.full((n, length), -1, np.int32)
    for i, r in enumerate(sample):
        prompt = np.asarray(r.request.prompt, np.int32)
        out = np.asarray(r.request.output, np.int32)
        p = len(prompt)
        seq = np.concatenate([prompt, out[:-1]])
        tokens[i, :len(seq)] = seq
        served[i, p - 1:p - 1 + len(out)] = out
    return tokens, served
