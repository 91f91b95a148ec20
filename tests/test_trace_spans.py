"""The program's own tracing: the serving engine's host spans on the
profiler's clock (serve/engine.py), the model's device scopes
(models/lm.py, models/attention.py), a stable name on every Pallas kernel,
and the serve CLI's ``--trace-dir``."""

import ast
import glob
import sys
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import configs
from repro.core.quant import QuantConfig
from repro.launch import steps as steps_lib
from repro.models import lm
from repro.serve import engine as engine_lib
from repro.serve.config import EngineConfig
from repro.serve.engine import Request, ServingEngine

KERNELS = Path(__file__).resolve().parents[1] / "src" / "repro" / "kernels"
PASS_CHILDREN = {
    "engine.prefill_pass": {"engine.batch", "engine.launch.prefill",
                            "engine.logits", "engine.sample"},
    "engine.decode_pass": {"engine.batch", "engine.launch.decode",
                           "engine.logits", "engine.sample"},
    "engine.speculative_pass": {"engine.batch", "engine.launch.draft",
                                "engine.drafted", "engine.launch.verify",
                                "engine.logits", "engine.accept"},
}


def float_cfg(kv_bits=4):
    return configs.get_config("stablelm-1.6b", reduced=True).replace(
        param_dtype="float32", compute_dtype="float32",
        quant=QuantConfig(enabled=False, kv_bits=kv_bits))


def host_spans(trace_dir):
    """(name, start_ns, end_ns, stats) of every ``engine.*`` host event."""
    path, = glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine"):
                    out.append((ev.name, int(ev.start_ns), int(ev.end_ns),
                                dict(ev.stats)))
    return out


def traced_run(trace_dir, **engine_kw):
    """Three requests of 20 prompt tokens through a paged, prefix-sharing
    engine (16-token pages: each prompt's tail page is registered, so each
    request's first decode write copies it), traced after a warm-up."""
    cfg = float_cfg()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, config=EngineConfig(
        max_batch=4, max_len=48, prefill_chunk=16, packed=False, paged=True,
        page_size=16, **engine_kw))
    rng = np.random.default_rng(3)

    def serve(uids):
        for uid in uids:
            assert eng.submit(Request(uid=uid, prompt=rng.integers(
                0, cfg.vocab_size, 20).astype(np.int32), max_new_tokens=4))
        return eng.run_to_completion()

    serve([-1])                                   # compile outside
    cows = eng.pool.cow_copies
    jax.profiler.start_trace(str(trace_dir))
    done = serve([100, 101, 102])
    jax.profiler.stop_trace()
    assert len(done) == 3
    return host_spans(trace_dir), eng.pool.cow_copies - cows


@pytest.fixture(scope="module")
def plain(tmp_path_factory):
    return traced_run(tmp_path_factory.mktemp("plain"))


@pytest.fixture(scope="module")
def speculative(tmp_path_factory):
    return traced_run(tmp_path_factory.mktemp("spec"), speculative_k=2)


def inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_every_span_reads_back_under_its_plain_name(plain):
    spans, _ = plain
    names = {s[0] for s in spans}
    assert names == {"engine.step", "engine.admit", "engine.prefill_pass",
                     "engine.decode_pass", "engine.batch", "engine.cow",
                     "engine.launch.prefill", "engine.launch.decode",
                     "engine.logits", "engine.sample"}
    # metadata rides as stats, never in the name
    admitted = [s[3]["uids"] for s in spans
                if s[0] == "engine.admit" and "uids" in s[3]]
    assert admitted == ["100 101 102"]
    prefill = [s[3].get("uids") for s in spans
               if s[0] == "engine.prefill_pass"]
    assert prefill and all(u == "100 101 102" for u in prefill)


@pytest.mark.parametrize("pass_name", ["engine.prefill_pass",
                                       "engine.decode_pass"])
def test_each_pass_holds_its_children(plain, pass_name):
    spans, _ = plain
    steps = [s for s in spans if s[0] == "engine.step"]
    passes = [s for s in spans if s[0] == pass_name]
    assert passes
    for p in passes:
        assert any(inside(p, st) for st in steps)
        kids = {s[0] for s in spans if s is not p and inside(s, p)}
        assert PASS_CHILDREN[pass_name] <= kids
    for s in spans:     # a child of this kind sits inside a pass
        if s[0] in ("engine.launch.prefill", "engine.launch.decode",
                    "engine.logits"):
            assert any(inside(s, p) for p in spans
                       if p[0] in PASS_CHILDREN)


def test_one_cow_span_per_copied_page(plain):
    spans, cows = plain
    cow = [s for s in spans if s[0] == "engine.cow"]
    assert cows == 3 and len(cow) == cows
    batches = [s for s in spans if s[0] == "engine.batch"]
    for s in cow:
        assert any(inside(s, b) for b in batches)
        assert s[3]["src"] != s[3]["dst"]


def test_speculative_pass_spans(speculative):
    spans, _ = speculative
    names = {s[0] for s in spans}
    assert {"engine.speculative_pass", "engine.draft_prefill",
            "engine.launch.draft_prefill"} <= names
    passes = [s for s in spans if s[0] == "engine.speculative_pass"]
    assert passes
    for p in passes:
        kids = {s[0] for s in spans if s is not p and inside(s, p)}
        assert PASS_CHILDREN["engine.speculative_pass"] <= kids


def test_metadata_is_built_only_while_tracing():
    consumed = []

    def reqs():
        consumed.append(True)
        yield Request(uid=1, prompt=np.zeros(2, np.int32))

    assert not engine_lib.Span.is_enabled()
    with engine_lib.Span("engine.x") as span:
        engine_lib._uids(span, reqs())
    assert not consumed


def pallas_call_names(path):
    names = []
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Call) and getattr(
                node.func, "attr", getattr(node.func, "id", "")) \
                == "pallas_call":
            kw = {k.arg: k.value for k in node.keywords}
            assert "name" in kw, f"{path.name}:{node.lineno} has no name="
            names.append(kw["name"].value)
    return names


def test_every_pallas_call_has_a_stable_name():
    names = {p.name: pallas_call_names(p)
             for p in sorted(KERNELS.glob("*.py"))}
    assert {k: v for k, v in names.items() if v} == {
        "quant_pack.py": ["quantize_pack"],
        "ulppack_attention.py": ["ulppack_attention_decode"],
        "ulppack_conv2d.py": ["ulppack_conv2d"],
        "ulppack_matmul.py": ["ulppack_matmul", "ulppack_matmul"]}


def test_decode_step_ops_carry_their_scopes():
    """Every scope the benchmark's readers select on appears in the
    compiled decode step's op metadata, under its layer."""
    cfg = float_cfg()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    caches = lm.init_caches(cfg, 2, 32, dtype=jax.numpy.float32,
                            page_size=16, num_pages=4)
    step = jax.jit(steps_lib.make_decode_step(cfg))
    hlo = step.lower(
        params, caches, {"tokens": np.zeros((2, 1), np.int32)},
        np.array([3, 5], np.int32), np.ones(2, np.int32),
        np.array([[0, 1], [2, 3]], np.int32)).compile().as_text()
    for scope in ("embed/", "layer_0/attn/qkv/", "layer_0/attn/kv_write/",
                  "layer_0/attn/core/", "layer_0/attn/out/",
                  "layer_1/mlp/", "head/"):
        assert f"jit(decode_step)/{scope}" in hlo, scope


def test_serve_cli_records_a_trace(tmp_path, monkeypatch):
    from repro.launch import serve
    # the CLI's persistent compile cache stays out of the test process
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "stablelm-1.6b", "--reduced", "--requests", "2",
        "--prompt-len", "4", "--max-new-tokens", "2", "--no-packed",
        "--trace-dir", str(tmp_path)])
    serve.main()
    names = {s[0] for s in host_spans(tmp_path)}
    assert {"engine.step", "engine.decode_pass", "engine.sample"} <= names
