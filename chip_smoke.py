"""On-chip smoke run of the serving main path at full published width.

    python chip_smoke.py             # one TPU chip: kernel + engine phases
    python chip_smoke.py --chips 4   # four chips: tensor-parallel engine,
                                     # 4-replica router, one-chip engine

One chip (the default):

* kernel phase — compiles and runs the three serving kernels
  (``quantize_pack``, ``packed_matmul``, ``attention_decode``) with
  ``interpret=False`` at stablelm-1.6b's served shapes and compares each
  with the ``xla`` backend on the same chip: bit-exact for the integer ops,
  within ``ATTN_ATOL``/``ATTN_RTOL`` for attention;
* engine phase — builds a ``ServingEngine`` (the path
  ``python -m repro.launch.serve`` takes) for the full-width model (24
  layers, d 2048, vocab 100352, W2A2) with random weights from ``--seed``
  and the paged 4-bit KV cache, serves seeded greedy requests (prompts of
  64-512 tokens, 32 new tokens each), and checks that every request's
  first token is the argmax of an uncached packed forward of its prompt.
  It also prints how often the first tokens agree with the same forward
  read through the legacy dequantizing attention (another algorithm, so
  another rounding; a reading, not a check).

Four chips (``--chips 4``) runs only what exists across chips: the same
requests (prompts of 64-512 tokens) through a 4-way tensor-parallel
engine and through a ``Router`` over four one-chip replicas, compared
token for token with a one-chip engine, at full width and
``FOUR_CHIP_LAYERS`` layers; it prints every device's memory and checks
that the compiled tensor-parallel decode step gathers no packed weight.

Everything runs in this one process and no child is started: a chip
belongs to one process at a time.  Without a TPU the script exits
non-zero before any phase.  Any failed check exits non-zero.  The last
line of stdout is one JSON object naming the device; everything else is
printed before it.  Tokens/s printed here are smoke readings, not a
benchmark.
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import sys
import time
import traceback
from pathlib import Path

ARCH = "stablelm-1.6b"
KV_BITS = 4
MAX_BATCH = 8
PREFILL_CHUNK = 32
NEW_TOKENS = 32
PROMPT_LENS = (64, 512)
#: the four-chip phase compiles six engines' steps (one chip, 4-way TP,
#: four router replicas: a jit executable is per device assignment); it
#: keeps full width and cuts depth to keep that compile to minutes
FOUR_CHIP_LAYERS = 4
#: attention is float: Pallas vs the xla backend (at HIGHEST matmul
#: precision) agree to bf16 output rounding plus f32 reduction order
ATTN_ATOL = 1e-2
ATTN_RTOL = 1e-2


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(*a):
    print(*a, flush=True)


# ---------------------------------------------------------------------------
# Bookkeeping: compile time and the kernel plans the path dispatched
# ---------------------------------------------------------------------------

class CompileClock:
    """Sums JAX's own trace + lower + compile durations (persistent-cache
    loads included) and counts persistent-cache hits."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self.EVENTS:
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class PlanLog:
    """Records every KernelPlan dispatched while tracing (and the query
    window width for attention), by wrapping kernels/plan.dispatch."""

    def __init__(self, plan_lib):
        self.seen: dict = {}
        orig = plan_lib.dispatch

        def dispatch(plan, *args, **kw):
            width = args[0].shape[1] if plan.op == "attention_decode" else 0
            self.seen.setdefault((plan, width), 0)
            self.seen[(plan, width)] += 1
            return orig(plan, *args, **kw)

        plan_lib.dispatch = dispatch

    def report_and_check(self):
        serving = ("packed_matmul", "quantize_pack", "attention_decode")
        for (plan, width), n in sorted(self.seen.items(),
                                       key=lambda kv: str(kv[0][0])):
            d = plan.describe()
            d["interpret"] = plan.interpret
            d["traces"] = n
            if plan.op == "attention_decode":
                d["query_rows"] = width
            log("plan", json.dumps(d, sort_keys=True))
            check(not plan.interpret,
                  f"plan runs in the Pallas interpreter: {plan}")
            if plan.op not in serving:
                continue
            # the Pallas decode-attention kernel takes one query row; the
            # chunked-prefill window (rows > 1) reads the cache on xla
            want = "xla" if plan.op == "attention_decode" and width > 1 \
                else "pallas"
            check(plan.backend == want,
                  f"{plan.op} (query rows {width}) ran on {plan.backend}, "
                  f"expected {want}: {plan}")
        ops = {p.op for p, _ in self.seen}
        for op in serving:
            check(op in ops, f"no {op} plan was dispatched")


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def full_config(configs):
    cfg = configs.get_config(ARCH)
    cfg = cfg.replace(quant=cfg.quant.replace(kv_bits=KV_BITS))
    check((cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.d_ff,
           cfg.vocab_size) == (24, 2048, 32, 5632, 100352),
          f"{ARCH} is not at its published widths: {cfg}")
    return cfg


def seeded_prompts(np, cfg, seed, n):
    rng = np.random.default_rng(seed)
    lo, hi = PROMPT_LENS
    lens = [lo, hi] + [int(x) for x in rng.integers(lo, hi + 1, n - 2)]
    return [rng.integers(0, cfg.vocab_size, n_tok).astype(np.int32)
            for n_tok in lens[:n]]


def kernel_phase(jax, jnp, np, cfg, seed):
    """Each serving kernel compiled (interpret=False) at served shapes,
    against the xla backend on the same chip."""
    from repro.core import packing
    from repro.core.packing import PackSpec
    from repro.kernels import ops, ulppack_attention  # noqa: F401 (backends)
    from repro.kernels import plan as plan_lib

    rng = np.random.default_rng(seed)
    spec = PackSpec.from_config(cfg.quant)
    d, ff = cfg.d_model, cfg.d_ff

    def pallas(plan):
        check(plan.backend == "pallas" and not plan.interpret,
              f"kernel phase needs compiled Pallas plans, got {plan}")
        return plan

    for m, k in ((MAX_BATCH, d), (MAX_BATCH * PREFILL_CHUNK, ff)):
        x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
        scale, zp = jnp.float32(0.6), jnp.int32(2)
        pp = pallas(plan_lib.plan_quantize_pack(m, k, spec,
                                                backend="pallas"))
        px = plan_lib.plan_quantize_pack(m, k, spec, backend="xla")
        got = jax.block_until_ready(plan_lib.dispatch(pp, x, scale, zp))
        want = plan_lib.dispatch(px, x, scale, zp)
        for g, w in zip(got, want):
            check(np.array_equal(np.asarray(g), np.asarray(w)),
                  f"quantize_pack {m}x{k}: pallas != xla")
        log(f"kernel quantize_pack {m}x{k}: bit-exact vs xla ({pp})")

    for m in (MAX_BATCH, MAX_BATCH * PREFILL_CHUNK):
        for k, n in ((d, d), (d, ff), (ff, d)):
            q_a = jnp.asarray(rng.integers(0, spec.max_a + 1, (m, k)),
                              jnp.int32)
            q_w = jnp.asarray(rng.integers(0, spec.max_w + 1, (k, n)),
                              jnp.int32)
            ap = packing.pack_activations(q_a, spec, axis=-1)
            wp = packing.pack_weights(q_w, spec, axis=0)
            kp = ap.shape[-1]
            pp = pallas(plan_lib.plan_packed_matmul(m, kp, n, spec,
                                                    backend="pallas"))
            px = plan_lib.plan_packed_matmul(m, kp, n, spec, backend="xla")
            got = np.asarray(plan_lib.dispatch(pp, ap, wp))
            want = np.asarray(plan_lib.dispatch(px, ap, wp))
            check(np.array_equal(got, want),
                  f"packed_matmul m={m} {k}->{n}: pallas != xla")
            log(f"kernel packed_matmul m={m} {k}->{n}: bit-exact vs xla "
                f"({pp})")

    b, h, kvh, hd, ps = MAX_BATCH, cfg.num_heads, cfg.num_kv_heads, \
        cfg.resolved_head_dim, 16
    n_pages = -(-(PROMPT_LENS[1] + NEW_TOKENS) // ps)
    pool = b * n_pages
    for bits in (4, 2):
        words = -(-hd // (32 // bits))
        cache = {        # the lane-flat pool, [pool, ps, kvh * words]
            "k": jnp.asarray(rng.integers(-2**31, 2**31, (pool, ps,
                                                           kvh * words)),
                             jnp.int32),
            "v": jnp.asarray(rng.integers(-2**31, 2**31, (pool, ps,
                                                           kvh * words)),
                             jnp.int32),
            "k_scale": jnp.asarray(rng.uniform(0.02, 0.2, (pool, ps, kvh)),
                                   jnp.bfloat16),
            "v_scale": jnp.asarray(rng.uniform(0.02, 0.2, (pool, ps, kvh)),
                                   jnp.bfloat16)}
        bt = jnp.asarray(rng.permutation(pool).reshape(b, n_pages),
                         jnp.int32)
        vlen = rng.integers(1, n_pages * ps + 1, b)
        vlen[0] = n_pages * ps
        valid = jnp.asarray(vlen, jnp.int32)
        qpos = valid[:, None] - 1
        q = jnp.asarray(rng.normal(size=(b, 1, h, hd)), jnp.bfloat16)
        pp = pallas(plan_lib.plan_attention_decode(
            b, n_pages * ps, h, kvh, hd, bits, page_size=ps,
            backend="pallas"))
        px = plan_lib.plan_attention_decode(
            b, n_pages * ps, h, kvh, hd, bits, page_size=ps, backend="xla")
        got = np.asarray(plan_lib.dispatch(
            pp, q, cache, valid, qpos, kv_bits=bits, hd=hd,
            block_tables=bt), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(plan_lib.dispatch(
                px, q, cache, valid, qpos, kv_bits=bits, hd=hd,
                block_tables=bt), np.float32)
        err = np.abs(got - want)
        bound = ATTN_ATOL + ATTN_RTOL * np.abs(want)
        check(np.isfinite(got).all() and (err <= bound).all(),
              f"attention_decode kv_bits={bits}: max |pallas - xla| "
              f"{err.max()} exceeds {ATTN_ATOL} + {ATTN_RTOL}*|xla|")
        log(f"kernel attention_decode paged kv_bits={bits} b={b} h={h} "
            f"kvh={kvh} hd={hd} pages={n_pages}x{ps}: max |pallas - xla| "
            f"{float(err.max())} within {ATTN_ATOL} + {ATTN_RTOL}*|xla| "
            f"({pp})")


def build_params(jax, lm, cfg, seed):
    """Random weights from ``seed``, each weight step calibrated by absmax.
    Under the LSQ step init, untrained W2 weights quantize with a biased
    mean whose common term swamps the prompt at full width: every prompt
    would get the same tokens, and the token checks would compare
    constants."""
    from repro.core import quant

    def absmax_steps(node):
        if isinstance(node, list):
            return [absmax_steps(v) for v in node]
        if not isinstance(node, dict):
            return node
        if "w_step" in node:
            node = dict(node, w_step=quant.calibrate_absmax(
                node["kernel"].astype(jax.numpy.float32),
                cfg.quant.w_bits)[0])
        return {k: absmax_steps(v) for k, v in node.items()}

    t0 = time.perf_counter()
    params = jax.block_until_ready(absmax_steps(
        lm.init_params(jax.random.PRNGKey(seed), cfg)))
    log(f"random full-width params (seed {seed}) in "
        f"{time.perf_counter() - t0:.1f}s")
    return params


def engine_config():
    from repro.serve.config import EngineConfig
    return EngineConfig(max_batch=MAX_BATCH,
                        max_len=PROMPT_LENS[1] + NEW_TOKENS, packed=True,
                        paged=True, page_size=16,
                        prefill_chunk=PREFILL_CHUNK)


def serve(engine, prompts):
    """Greedy requests through an engine; returns {uid: tokens}."""
    from repro.serve.engine import Request
    for i, p in enumerate(prompts):
        check(engine.submit(Request(uid=i, prompt=p,
                                    max_new_tokens=NEW_TOKENS)),
              f"request {i} rejected")
    done = engine.run_to_completion()
    out = {r.uid: tuple(int(t) for t in r.output) for r in done}
    check(sorted(out) == list(range(len(prompts))),
          f"finished {sorted(out)} of {len(prompts)} requests")
    for uid, toks in out.items():
        check(len(toks) == NEW_TOKENS,
              f"request {uid} produced {len(toks)} of {NEW_TOKENS} tokens")
    return out


def uncached_last_logits(jax, jnp, np, lm, params, cfg, prompts):
    """f32 logits [n, vocab] of an uncached packed forward at each prompt's
    last token: one window of the prompts right-padded to the longest
    (causal attention keeps the pad out of every real position), no KV
    cache, no pages, no chunks."""
    toks = np.zeros((len(prompts), max(len(p) for p in prompts)), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    last = jnp.asarray([len(p) - 1 for p in prompts], jnp.int32)
    from repro.launch.steps import SERVING_XLA_OPTIONS

    @functools.partial(jax.jit, compiler_options=SERVING_XLA_OPTIONS)
    def first(params, tokens, last):
        logits, _, _ = lm.forward(params, cfg, {"tokens": tokens},
                                  quant_mode="packed")
        return logits[jnp.arange(tokens.shape[0]), last].astype(jnp.float32)

    return np.asarray(first(params, jnp.asarray(toks), last))


def engine_phase(jax, jnp, np, cfg, seed, clock, dev):
    from repro.models import lm
    from repro.serve.engine import Metrics, ServingEngine

    params = build_params(jax, lm, cfg, seed)
    t0 = time.perf_counter()
    eng = ServingEngine(cfg, params, config=engine_config())
    del params
    log(f"engine built in {time.perf_counter() - t0:.1f}s: "
        f"max_batch {eng.max_batch}, pages {eng.num_pages}x"
        f"{eng.page_size}, kv_bits {cfg.quant.kv_bits}, W{cfg.quant.w_bits}"
        f"A{cfg.quant.a_bits}")

    # warm-up request: compiles the prefill-chunk and decode steps
    c0, h0, t0 = clock.seconds, clock.cache_hits, time.perf_counter()
    rng = np.random.default_rng(seed + 1)
    serve_warm = [rng.integers(0, cfg.vocab_size, PREFILL_CHUNK + 8)
                  .astype(np.int32)]
    from repro.serve.engine import Request
    eng.submit(Request(uid=0, prompt=serve_warm[0], max_new_tokens=2))
    eng.run_to_completion()
    compile_s = clock.seconds - c0
    log(f"engine warm-up (compiles prefill + decode steps): "
        f"{time.perf_counter() - t0:.1f}s wall, compile {compile_s:.1f}s, "
        f"persistent-cache hits {clock.cache_hits - h0}")
    eng.metrics = Metrics()

    prompts = seeded_prompts(np, cfg, seed, 6)
    t0 = time.perf_counter()
    out = serve(eng, prompts)
    wall = time.perf_counter() - t0
    rep = eng.metrics.report()
    gen = sum(len(t) for t in out.values())
    log(f"served {len(prompts)} requests, prompt lengths "
        f"{[len(p) for p in prompts]}, {gen} tokens generated in "
        f"{wall:.1f}s")
    log(f"decode {rep['decode_tok_s']} tok/s, prefill "
        f"{rep['prefill_tok_s']} tok/s (smoke reading, not a benchmark)")

    from repro.kernels import ulppack_attention
    c0 = clock.seconds
    rows = uncached_last_logits(jax, jnp, np, lm, eng.params, cfg, prompts)
    log(f"uncached packed forward compiled in {clock.seconds - c0:.1f}s")
    with ulppack_attention.disabled():
        legacy = uncached_last_logits(jax, jnp, np, lm, eng.params, cfg,
                                      prompts)
    for uid, toks in sorted(out.items()):
        row = rows[uid]
        margin = float(row.max() - np.partition(row, -2)[-2])
        log(f"request {uid}: first token {toks[0]}, uncached argmax "
            f"{int(row.argmax())} (top-2 logit margin {margin}); legacy-read "
            f"argmax {int(legacy[uid].argmax())}, max |logit diff| "
            f"{float(np.abs(legacy[uid] - row).max())}")
    log(f"first tokens equal to the legacy-read argmax: "
        f"{sum(int(legacy[u].argmax()) == t[0] for u, t in out.items())} "
        f"of {len(out)} (a reading: another rounding of the same model, "
        f"which 24 random W2A2 layers amplify)")
    for uid, toks in sorted(out.items()):
        check(toks[0] == int(rows[uid].argmax()),
              f"request {uid}: engine first token {toks[0]} is not the "
              f"uncached forward's argmax {int(rows[uid].argmax())}")
    log(f"first tokens: all {len(out)} equal the uncached argmax")
    log(f"distinct first tokens {len({t[0] for t in out.values()})} of "
        f"{len(out)}, distinct generated tokens "
        f"{len({x for t in out.values() for x in t})}")
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
        f"(bytes_limit {stats.get('bytes_limit')})")
    log(f"compile seconds (all phases so far) {clock.seconds:.1f}, "
        f"persistent-cache hits {clock.cache_hits}")
    return compile_s


def leaf_devices(tree):
    import jax
    ids = set()
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "devices"):
            ids |= {d.id for d in leaf.devices()}
    return sorted(ids)


def memory_report(jax):
    used = {}
    for d in jax.devices():
        st = d.memory_stats() or {}
        used[d.id] = st.get("bytes_in_use", 0)
        log(f"device {d.id} memory_stats "
            + json.dumps({k: st.get(k) for k in (
                "bytes_in_use", "peak_bytes_in_use", "bytes_limit")}))
    return used


def weight_gathers(hlo: str, params) -> list:
    """all-gather instructions in compiled HLO whose result has the global
    shape of a packed weight (a gathered weight operand)."""
    import jax
    import re
    shapes = set()
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        if str(path[-1]).strip("[]'") in ("w_packed", "w_dense"):
            shapes.add(",".join(str(s) for s in leaf.shape))
    bad = []
    for line in hlo.splitlines():
        if "all-gather" not in line:
            continue
        m = re.search(r"=\s*\w+\[([\d,]+)\]", line)
        if m and m.group(1) in shapes:
            bad.append(line.strip())
    return bad


def four_chip_phase(jax, jnp, np, cfg, seed, clock):
    from repro.launch.mesh import make_serving_mesh
    from repro.models import lm
    from repro.serve.engine import ServingEngine
    from repro.serve.router import Router

    cfg = cfg.replace(num_layers=FOUR_CHIP_LAYERS)
    log(f"four-chip phase: {ARCH} at full width, depth cut to "
        f"{cfg.num_layers} layers (the cross-chip layout is per layer; "
        f"each replica and mesh compiles its own steps)")
    params = build_params(jax, lm, cfg, seed)
    prompts = seeded_prompts(np, cfg, seed, 4)
    log(f"prompt lengths {[len(p) for p in prompts]}")
    econf = engine_config()

    t0 = time.perf_counter()
    eng = ServingEngine(cfg, params, config=econf)
    log(f"one-chip engine: params on devices {leaf_devices(eng.params)}")
    single = serve(eng, prompts)
    log(f"one-chip engine served {len(single)} requests in "
        f"{time.perf_counter() - t0:.1f}s (compile {clock.seconds:.1f}s "
        f"so far)")
    del eng
    gc.collect()

    t0 = time.perf_counter()
    mesh = make_serving_mesh(model=4)
    eng = ServingEngine(cfg, params, config=econf, mesh=mesh)
    log(f"tensor-parallel engine: mesh {dict(mesh.shape)} devices "
        f"{[d.id for d in mesh.devices.flat]}")
    w = eng.params["layers"][0]["attn"]["q"]["w_packed"]
    for sh in w.addressable_shards:
        log(f"  layers[0]/attn/q/w_packed shard on device {sh.device.id}: "
            f"{sh.data.shape} of {w.shape}")
    tp = serve(eng, prompts)
    log(f"tensor-parallel engine served {len(tp)} requests in "
        f"{time.perf_counter() - t0:.1f}s")
    used = memory_report(jax)
    packed_bytes = sum(x.size * x.dtype.itemsize
                       for x in jax.tree.leaves(eng.params)
                       if hasattr(x, "dtype") and x.dtype == w.dtype)
    for d in mesh.devices.flat:
        check(used[d.id] >= packed_bytes // 4,
              f"device {d.id} holds {used[d.id]} bytes, less than its "
              f"quarter of the packed weights ({packed_bytes // 4})")
    batch = {"tokens": jnp.zeros((eng.max_batch, 1), jnp.int32)}
    idx = jnp.zeros((eng.max_batch,), jnp.int32)
    with eng._mesh_ctx():
        hlo = eng._decode.lower(eng.params, eng.caches, batch, idx, idx,
                                jnp.asarray(eng.block_tables)
                                ).compile().as_text()
    gathers = weight_gathers(hlo, eng.params)
    log(f"tensor-parallel decode step: {hlo.count('tpu_custom_call')} "
        f"tpu_custom_call, {hlo.count('all-gather(')} all-gather, "
        f"{len(gathers)} of them gather a packed weight")
    check("tpu_custom_call" in hlo, "no Pallas kernel in the TP decode step")
    check(not gathers, f"packed weights all-gathered: {gathers[:3]}")
    del eng
    gc.collect()

    t0 = time.perf_counter()
    fleet_mesh = make_serving_mesh(model=1, data=4)
    router = Router(cfg, params, config=econf, mesh=fleet_mesh)
    for i, e in enumerate(router.engines):
        log(f"router replica {i}: mesh devices "
            f"{[d.id for d in e.mesh.devices.flat]}, params on devices "
            f"{leaf_devices(e.params)}")
    handles = [router.submit(p, max_new_tokens=NEW_TOKENS)
               for p in prompts]
    router.run_to_completion()
    fleet = {i: tuple(int(t) for t in h.output)
             for i, h in enumerate(handles)}
    placed = [r["admitted"] if r else 0
              for r in router.metrics_report()["replica_reports"]]
    log(f"router served {len(fleet)} requests in "
        f"{time.perf_counter() - t0:.1f}s (admitted per replica {placed})")
    memory_report(jax)

    for uid in sorted(single):
        log(f"request {uid}: one-chip {single[uid][:8]}..., "
            f"tp {tp[uid][:8]}..., router {fleet[uid][:8]}...")
    def forks(other):
        """{uid: first token index where ``other`` leaves the one-chip
        tokens} for every request that differs."""
        return {u: next(i for i, (a, b) in enumerate(zip(t, other[u]))
                        if a != b)
                for u, t in single.items() if t != other[u]}

    check(tp == single,
          f"tensor-parallel tokens differ from one chip at {forks(tp)}")
    check(fleet == single,
          f"router tokens differ from one chip at {forks(fleet)}")
    log("greedy tokens identical: one chip == 4-way TP == 4-replica router")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro" / "serve" / "engine.py").is_file():
        print(f"chip_smoke: no repro package under {src}; run from the "
              f"repository checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform} "
              f"({len(devices)} device(s)); nothing was run",
              file=sys.stderr)
        return 3
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 3

    from repro import configs
    from repro.kernels import autotune
    from repro.kernels import plan as plan_lib

    clock = CompileClock(jax)
    log(f"device_kind {dev.device_kind}, platform {dev.platform}, "
        f"device count {len(devices)}, jax {jax.__version__}")
    log(f"compile cache {cache_dir}; autotune cache "
        f"{autotune.default_cache_path()} "
        f"({'present' if Path(autotune.default_cache_path()).is_file() else 'absent: plans are heuristic'})")
    cfg = full_config(configs)
    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            kernel_phase(jax, jnp, np, cfg, args.seed)
            # plans are logged from here on: the serving path's own, not
            # the kernel phase's deliberate xla comparisons
            plans = PlanLog(plan_lib)
            engine_phase(jax, jnp, np, cfg, args.seed, clock, dev)
        else:
            plans = PlanLog(plan_lib)
            four_chip_phase(jax, jnp, np, cfg, args.seed, clock)
        plans.report_and_check()
    except Exception:
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    log(f"total {time.perf_counter() - t0:.1f}s, compile "
        f"{clock.seconds:.1f}s, persistent-cache hits {clock.cache_hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
