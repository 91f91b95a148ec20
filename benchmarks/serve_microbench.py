"""Serving microbench, three levels (DESIGN.md §12, §13):

* ``run_linear`` — bf16 vs unpacked-int vs packed ULPPACK paths at decode
  shapes, on CPU XLA (wall-clock) + compiled FLOP/byte counts.  The
  LM-integration counterpart of fig4 (which benches the paper's conv2d).
* ``run_engine`` — engine-level before/after: chunked-prefill continuous
  batching (``ServingEngine`` with prefill_chunk > 1) against the
  token-at-a-time admission baseline (prefill_chunk=1) at prompt length
  64, reporting the scheduler Metrics (prefill/decode tokens/s, slot
  occupancy).  This is the end-to-end number the paper's thesis is about:
  kernels only pay off when the serving layer keeps them fed.
* ``run_kv_cache`` — cache-bytes-per-slot + decode tok/s at kv_bits in
  {16, 8, 4, 2} under one fixed HBM cache budget: the sub-byte packed KV
  cache converts bit density into admission capacity (slots scale with the
  bytes shrink), the serving-side analogue of the paper's sub-byte storage
  thesis.
* ``run_paged`` — paged, prefix-sharing KV cache (serve/pages.py,
  DESIGN.md §18) vs the slot-contiguous cache under one fixed HBM budget
  on a 64-token shared-prefix workload: peak concurrent sequences,
  prefix-share ratio, COW/page counters, and token identity.  Report-only
  by metric naming; tests/test_paged_kv.py gates the semantics.
* ``run_sharded`` — tensor-parallel packed engine (serve/shard.ShardPlan,
  DESIGN.md §15) vs the single-device engine on the same requests.
  Report-only (CPU-simulated meshes measure collective overhead, not TP
  scaling; the metric names deliberately avoid the gated speedup/_vs_bf16
  patterns) and degrades to a single row noting the device count when the
  host has one device (force more with
  XLA_FLAGS=--xla_force_host_platform_device_count=4).
* ``run_router`` — replica-fleet Router (serve/router.py, DESIGN.md §17)
  under a saturating request burst: 1 replica vs 2 replicas behind the
  load-balanced front door.  Report-only for the same reason: fleet
  decode tok/s is the SUM of per-replica rates (each replica models
  disjoint hardware; a process-local host shares one box), so
  ``decode_tok_s_ratio_vs_single`` states the fleet-aggregation model
  rather than measuring host speedup — tests/test_router.py gates the
  semantics (identity, spillover, affinity, drain/restore).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (cost_of, emit, record,
                               tuned_vs_heuristic_row, wall_us)
from repro.core import packing
from repro.core.packing import PackSpec
from repro.kernels import ops
from repro.kernels import plan as plan_lib


def run_linear(quick: bool = False):
    m = 8                       # decode rows per device
    k, n = (1024, 1024) if quick else (4096, 4096)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)) * 0.05, jnp.float32)
    rows = []

    wb16 = w.astype(jnp.bfloat16)

    def bf16(x):
        return jnp.dot(x.astype(jnp.bfloat16), wb16)

    c = cost_of(bf16, x)
    rows.append({"path": "bf16", "wall_us": round(wall_us(bf16, x), 1),
                 **c, "weight_bytes": wb16.size * 2})

    w8 = jnp.clip(jnp.round(w / 0.01), -127, 127).astype(jnp.int8)

    def int8(x):
        q = jnp.clip(jnp.round(x / 0.05), -127, 127).astype(jnp.int8)
        return ops.int_matmul(q, w8, backend="xla")

    c = cost_of(int8, x)
    rows.append({"path": "int8-unpacked", "wall_us": round(wall_us(int8, x),
                                                           1),
                 **c, "weight_bytes": w8.size})

    for wb, ab in ((1, 1), (2, 2), (3, 3)):
        spec = PackSpec(wb, ab, jnp.int16.dtype)
        wp, cs = ops.prepare_weights(w, jnp.float32(0.02), jnp.int32(
            1 << (wb - 1)), spec)

        def packed(x, wp=wp, cs=cs, spec=spec, wb=wb):
            return ops.quantized_linear(
                x, wp, cs, jnp.float32(0.07),
                jnp.int32(1 << (ab - 1)), jnp.float32(0.02),
                jnp.int32(1 << (wb - 1)), spec, backend="xla")

        c = cost_of(packed, x)
        rows.append({"path": f"packed-W{wb}A{ab}",
                     "wall_us": round(wall_us(packed, x), 1), **c,
                     "weight_bytes": wp.size * 2})

    emit(rows, ["path", "wall_us", "flops", "bytes", "weight_bytes"])
    rows += _tuned_vs_heuristic_linear()
    return rows


#: The decode-shaped linear the tuned-vs-heuristic row (and run.warm_tune)
#: benchmarks through the Pallas tile grid.
TUNED_LINEAR_SHAPE = (8, 256, 256)

# (b, skv, h, kvh, hd, kv_bits, page_size) of the gated attention-decode
# headline row; benchmarks/run.py warm-tunes exactly this signature so the
# committed autotune cache can never desync from the gate (DESIGN.md §20)
ATTN_DECODE_SHAPE = (4, 2048, 8, 4, 64, 2, 16)


def _tuned_vs_heuristic_linear():
    """Decode-shaped Pallas packed matmul under the autotuned plan vs the
    static heuristic (the fused-kernel tile grid is where the autotuner's
    wins live; the XLA rows above ignore tile choice).  Cache miss ->
    tuned == heuristic, speedup 1.0 (DESIGN.md §14)."""
    m, k, n = TUNED_LINEAR_SHAPE
    spec = PackSpec(2, 2, jnp.int16.dtype)
    rng = np.random.default_rng(1)
    q_a = jnp.asarray(rng.integers(0, spec.max_a + 1, (m, k)), jnp.int32)
    q_w = jnp.asarray(rng.integers(0, spec.max_w + 1, (k, n)), jnp.int32)
    ap = packing.pack_activations(q_a, spec, axis=-1)
    wp = packing.pack_weights(q_w, spec, axis=0)
    kp = ap.shape[-1]
    heur = plan_lib.plan_packed_matmul(m, kp, n, spec, backend="pallas",
                                       use_tuning_cache=False)
    tuned = plan_lib.plan_packed_matmul(m, kp, n, spec, backend="pallas")
    rows = [tuned_vs_heuristic_row(
        "tuned-vs-heuristic/packed-W2A2", heur, tuned,
        lambda plan: ops.packed_matmul(ap, wp, spec, plan=plan))]
    emit(rows, ["case", "heuristic_us", "tuned_us", "tuned_speedup",
                "plan_source", "plan"])
    rows += _layout_sweep_linear()
    return rows


def _layout_sweep_linear():
    """Chosen lane layout vs the fixed-layout heuristic at the decode
    linear shape (W2A2, lanes store), through the same Pallas dispatch —
    the matmul counterpart of fig4's layout-sweep row.

    The candidate layout comes from the committed layout cache
    (autotune.matmul_layout_for; warm-tuned by ``benchmarks.run
    --autotune``); each side packs its own operands, since the layout is
    the offline packing decision this axis tunes.  On a layout-cache miss
    the chosen spec IS the config default (speedup 1.0).  The chosen
    layout's output is asserted bit-exact against the unpacked int32
    reference before it is timed (DESIGN.md §16)."""
    from repro.kernels import autotune, ref

    m, k, n = TUNED_LINEAR_SHAPE
    base = PackSpec(2, 2, jnp.int16.dtype)
    rng = np.random.default_rng(2)
    q_a = jnp.asarray(rng.integers(0, base.max_a + 1, (m, k)), jnp.int32)
    q_w = jnp.asarray(rng.integers(0, base.max_w + 1, (k, n)), jnp.int32)
    want = np.asarray(ref.matmul_i32_ref(q_a, q_w))
    chosen = autotune.matmul_layout_for(k, n, base, backend="pallas",
                                        weight_store="lanes")

    def operands(spec):
        return (packing.pack_activations(q_a, spec, axis=-1),
                packing.pack_weights(q_w, spec, axis=0))

    ab, wb = operands(base)
    heur = plan_lib.plan_packed_matmul(m, ab.shape[-1], n, base,
                                       backend="pallas",
                                       use_tuning_cache=False)
    heur_us = wall_us(lambda: ops.packed_matmul(ab, wb, base, plan=heur),
                      iters=1, warmup=1)
    ac, wc = operands(chosen)
    tuned = plan_lib.plan_packed_matmul(m, ac.shape[-1], n, chosen,
                                        backend="pallas")
    got = ops.packed_matmul(ac, wc, chosen, plan=tuned)
    np.testing.assert_array_equal(np.asarray(got), want)
    tuned_us = heur_us if (chosen, tuned) == (base, heur) else wall_us(
        lambda: ops.packed_matmul(ac, wc, chosen, plan=tuned),
        iters=1, warmup=1)
    rows = [record("layout-sweep/linear",
                   heuristic_us=round(heur_us, 1),
                   tuned_us=round(tuned_us, 1),
                   tuned_speedup=round(heur_us / tuned_us, 2),
                   spec=str(chosen), base_spec=str(base),
                   plan_source=tuned.source, plan=str(tuned))]
    emit(rows, ["case", "heuristic_us", "tuned_us", "tuned_speedup",
                "spec", "base_spec", "plan_source", "plan"])
    return rows


PROMPT_LEN = 64


def run_engine(quick: bool = False):
    """Engine-level prefill/decode throughput: chunked prefill vs the
    token-at-a-time baseline (chunk=1) at prompt length 64."""
    from repro import configs
    from repro.core.quant import QuantConfig
    from repro.models import lm
    from repro.serve.config import EngineConfig
    from repro.serve.engine import Request, ServingEngine

    cfg = configs.get_config("stablelm-1.6b", reduced=True).replace(
        param_dtype="float32", compute_dtype="float32",
        quant=QuantConfig(enabled=False))
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    n_req = 2 if quick else 4
    max_batch = 2
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, PROMPT_LEN).astype(np.int32)
               for _ in range(n_req)]

    def bench(chunk):
        from repro.serve.engine import Metrics
        eng = ServingEngine(cfg, params, config=EngineConfig(
            max_batch=max_batch, max_len=PROMPT_LEN + 16, packed=False,
            prefill_chunk=chunk))
        # warmup: compile both jitted steps outside the measured window
        eng.submit(Request(uid=10_000, prompt=prompts[0],
                           max_new_tokens=4))
        eng.run_to_completion()
        eng.metrics = Metrics()
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=p, max_new_tokens=4))
        eng.run_to_completion()
        return eng.metrics.report()

    chunks = (1, 16) if quick else (1, 8, 16, 32)
    rows = []
    base = None
    for chunk in chunks:
        rep = bench(chunk)
        if chunk == 1:
            base = rep["prefill_tok_s"]
        rows.append({
            "engine": "token-at-a-time" if chunk == 1
            else f"chunked-prefill-{chunk}",
            "prefill_chunk": chunk,
            "prompt_len": PROMPT_LEN,
            "prefill_tok_s": rep["prefill_tok_s"],
            "decode_tok_s": rep["decode_tok_s"],
            "occupancy": rep["occupancy"],
            "steps": rep["steps"],
            "speedup_vs_baseline": round(rep["prefill_tok_s"] / base, 2)
            if base else 0.0,
        })
    emit(rows, ["engine", "prefill_chunk", "prompt_len", "prefill_tok_s",
                "decode_tok_s", "occupancy", "steps",
                "speedup_vs_baseline"])
    return rows


def run_kv_cache(quick: bool = False):
    """Cache bytes/slot + decode tok/s vs kv_bits under one HBM budget.

    The budget is fixed at ``base_slots`` bf16 slots; quantized caches admit
    budget // bytes-per-slot concurrent sequences, so the slots column shows
    the admission-capacity win (~2x int8, ~4x 4-bit, ~8x 2-bit) alongside
    the decode throughput of each storage layout.  head_dim=64 matches the
    full model (the reduced config's derived 16 would understate density:
    per-(pos, head) scales amortize over the head dim).
    """
    from repro import configs
    from repro.core.quant import QuantConfig
    from repro.models import lm
    from repro.serve.config import EngineConfig
    from repro.serve.engine import Metrics, Request, ServingEngine
    from repro.serve.prepare import cache_bytes_per_slot

    base = configs.get_config("stablelm-1.6b", reduced=True).replace(
        param_dtype="float32", compute_dtype="float32", head_dim=64,
        quant=QuantConfig(enabled=False))
    params = lm.init_params(jax.random.PRNGKey(0), base)
    max_len = 48
    base_slots = 2 if quick else 4
    budget = base_slots * cache_bytes_per_slot(base, max_len)
    prompt_len, new_tokens = 8, 4 if quick else 8
    rng = np.random.default_rng(0)

    rows = []
    ref = None
    for kv_bits in (16, 8, 4, 2):
        cfg = base.replace(quant=QuantConfig(
            enabled=False, kv_bits=0 if kv_bits == 16 else kv_bits))
        eng = ServingEngine(cfg, params, config=EngineConfig(
            max_len=max_len, packed=False, prefill_chunk=8,
            hbm_cache_budget=budget))
        n_req = eng.max_batch
        prompts = [rng.integers(0, cfg.vocab_size, prompt_len).astype(
            np.int32) for _ in range(n_req)]
        # warmup compiles both jitted steps outside the measured window
        eng.submit(Request(uid=10_000, prompt=prompts[0], max_new_tokens=2))
        eng.run_to_completion()
        eng.metrics = Metrics()
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=p,
                               max_new_tokens=new_tokens))
        eng.run_to_completion()
        rep = eng.metrics.report()
        cap = eng.capacity_report()
        if kv_bits == 16:
            ref = cap
        rows.append({
            "kv_bits": kv_bits,
            "cache_bytes_per_slot": cap["cache_bytes_per_slot"],
            "slots": cap["slots"],
            "decode_tok_s": rep["decode_tok_s"],
            "shrink_vs_bf16": round(ref["cache_bytes_per_slot"]
                                    / cap["cache_bytes_per_slot"], 2),
            "slots_vs_bf16": round(cap["slots"] / ref["slots"], 2),
        })
    emit(rows, ["kv_bits", "cache_bytes_per_slot", "slots", "decode_tok_s",
                "shrink_vs_bf16", "slots_vs_bf16"])
    return rows


def run_attention_decode(quick: bool = False):
    """Fused flash-decoding attention read vs the legacy decode path
    (kernels/ulppack_attention.py, DESIGN.md §20), same-run.

    The legacy path gathers the paged cache into its logical [B, S] view
    (unpaged: dequantizes the ring) and softmaxes one [B, H, S] score
    row; the fused read walks the stored cache in online-softmax groups
    and skips groups past the live high-water mark — so at serving
    shapes (2048-token allocation, ~520 live) it pays O(live) where the
    legacy path pays O(allocated).  ``attention_decode_speedup`` is a
    same-run ratio at the paged sub-byte headline shape and carries a
    hard floor; tests/test_fused_attention.py gates the numerics.  The
    long-context ENGINE case (512-token prompts, kv_bits=2, paged) is
    report-only: end-to-end decode tok/s where the fused read dominates
    the step.
    """
    from repro.kernels import ulppack_attention as ua
    from repro.models import attention as attn

    b, skv, h, kvh, hd, kv_bits, ps = ATTN_DECODE_SHAPE
    n_pages = skv // ps                   # 2048-token logical view
    size = skv
    live = 520
    rng = np.random.default_rng(0)

    def quantized(shape_rows):
        k = jnp.asarray(rng.normal(size=(shape_rows, ps, kvh, hd)),
                        jnp.float32)
        v = jnp.asarray(rng.normal(size=(shape_rows, ps, kvh, hd)),
                        jnp.float32)
        qk, sk = attn._kv_quantize(k, kv_bits)
        qv, sv = attn._kv_quantize(v, kv_bits)
        return {"k": qk, "v": qv, "k_scale": sk, "v_scale": sv}

    q = jnp.asarray(rng.normal(size=(b, 1, h, hd)), jnp.float32)
    vl = jnp.full((b,), live, jnp.int32)
    qpos = jnp.full((b, 1), live - 1, jnp.int32)

    def legacy(cache, bt):
        def fn(q, cache, vl, qpos):
            if bt is None:
                k, v = attn._cache_read(cache, jnp.float32, kv_bits, hd)
            else:
                k, v = attn._paged_cache_read(cache, bt, jnp.float32,
                                              kv_bits, hd)
            kv_pos = attn._ring_positions_batch(vl - 1, size, 0)
            mask = (kv_pos[:, None, :] <= qpos[:, :, None]) \
                & (kv_pos[:, None, :] >= 0)
            return attn._chunked_attention(
                q, lambda: (k, v), lambda _: mask, qpos, 1)
        return jax.jit(fn)

    def fused(bt):
        def fn(q, cache, vl, qpos):
            return ua.fused_decode_attention(q, cache, vl, qpos,
                                             kv_bits=kv_bits, hd=hd,
                                             block_tables=bt,
                                             backend="xla")
        return jax.jit(fn)

    rows = []
    for case, paged in (("attention-decode/paged-kv2", True),
                        ("attention-decode/contiguous-kv2", False)):
        if paged:         # the lane-flat pool, [P, ps, kvh * words]
            cache = {kk: vv.reshape(b * n_pages, ps, -1)
                     for kk, vv in quantized(b * n_pages).items()}
            bt = jnp.asarray(np.arange(b * n_pages).reshape(b, n_pages),
                             jnp.int32)
        else:
            pool = quantized(b * n_pages)
            cache = {kk: vv.reshape(b, size, *vv.shape[2:])
                     for kk, vv in pool.items()}
            bt = None
        old_fn, new_fn = legacy(cache, bt), fused(bt)
        diff = float(jnp.max(jnp.abs(new_fn(q, cache, vl, qpos)
                                     - old_fn(q, cache, vl, qpos))))
        old_us = wall_us(old_fn, q, cache, vl, qpos)
        new_us = wall_us(new_fn, q, cache, vl, qpos)
        row = {
            "case": case, "kv_bits": kv_bits, "alloc_tokens": size,
            "live_tokens": live, "page_size": ps if paged else 0,
            "legacy_us": round(old_us, 1), "fused_us": round(new_us, 1),
            "attention_decode_speedup": round(old_us / max(new_us, 1e-9),
                                              2),
            "max_abs_diff": round(diff, 7),
        }
        if paged:
            row["floor"] = {"attention_decode_speedup": 1.3}
        rows.append(row)

    # long-context engine case: report-only end-to-end tok/s
    from repro import configs
    from repro.core.quant import QuantConfig
    from repro.models import lm
    from repro.serve.config import EngineConfig
    from repro.serve.engine import Metrics, Request, ServingEngine

    cfg = configs.get_config("stablelm-1.6b", reduced=True).replace(
        param_dtype="float32", compute_dtype="float32", head_dim=64,
        quant=QuantConfig(enabled=False, kv_bits=2))
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompt_len, new_tokens = 512, 4 if quick else 8
    eng = ServingEngine(cfg, params, config=EngineConfig(
        max_batch=2, max_len=prompt_len + new_tokens + 2, packed=False,
        prefill_chunk=64, paged=True, page_size=ps))
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len)
               .astype(np.int32) for _ in range(2)]
    eng.submit(Request(uid=10_000, prompt=prompts[0], max_new_tokens=2))
    eng.run_to_completion()
    eng.metrics = Metrics()
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=new_tokens))
    eng.run_to_completion()
    rep = eng.metrics.report()
    rows.append({
        "case": "attention-decode/long-context-engine",
        "kv_bits": 2, "alloc_tokens": prompt_len + new_tokens + 2,
        "live_tokens": prompt_len, "page_size": ps,
        "prompt_len": prompt_len,
        "decode_tok_s": rep["decode_tok_s"],
        "prefill_tok_s": rep["prefill_tok_s"],
    })
    emit(rows, ["case", "kv_bits", "alloc_tokens", "live_tokens",
                "legacy_us", "fused_us", "attention_decode_speedup",
                "decode_tok_s"])
    return rows


def run_paged(quick: bool = False):
    """Paged, prefix-sharing KV cache vs the slot-contiguous cache under
    ONE fixed HBM budget on a shared-prefix workload (DESIGN.md §18).

    Every request shares a 64-token prefix and adds a short unique tail —
    the system-prompt shape paging exists for.  The unpaged engine sizes
    whole ``max_len`` slots from the budget (4 here); the paged engine
    spends the same bytes on a 4-bit page pool, primes the prefix cache
    with one warmup request, then admits every follow-up at ~2 fresh
    pages apiece — ``peak_live_slot_count`` / ``logical_slot_multiplier``
    show concurrent sequences at >= 2x the unpaged slot count, and
    ``prompt_rows_computed`` shows the prefill work the radix cache
    skipped.  Report-only by metric naming (counters and ratios carry no
    gated suffix); tests/test_paged_kv.py gates the token-identity and
    capacity semantics.
    """
    from repro import configs
    from repro.core.quant import QuantConfig
    from repro.models import lm
    from repro.serve.config import EngineConfig
    from repro.serve.engine import Metrics, Request, ServingEngine
    from repro.serve.prepare import cache_bytes_per_slot

    base = configs.get_config("stablelm-1.6b", reduced=True).replace(
        param_dtype="float32", compute_dtype="float32", head_dim=64,
        quant=QuantConfig(enabled=False, kv_bits=4))
    params = lm.init_params(jax.random.PRNGKey(0), base)
    page_size, max_len = 16, 80
    budget = 4 * cache_bytes_per_slot(base, max_len)
    n_req, new_tokens = 12, 2 if quick else 4
    tail_len = 4
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, base.vocab_size, PROMPT_LEN).astype(np.int32)
    prompts = [np.concatenate([prefix, rng.integers(
        0, base.vocab_size, tail_len).astype(np.int32)])
        for _ in range(n_req)]

    def bench(econf, warm_prefix):
        eng = ServingEngine(base, params, config=econf)
        # warmup compiles both steps; for the paged engine it also primes
        # the radix prefix cache (a system prompt being cached once)
        eng.submit(Request(uid=10_000,
                           prompt=prefix if warm_prefix else prompts[0],
                           max_new_tokens=2))
        eng.run_to_completion()
        eng.metrics = Metrics()
        if warm_prefix:
            eng.peak_live_slots = 0
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=p, max_new_tokens=new_tokens))
        outs = {r.uid: tuple(r.output) for r in eng.run_to_completion()}
        return eng.metrics.report(), eng.capacity_report(), outs

    unpaged_rep, unpaged_cap, unpaged_out = bench(
        EngineConfig(max_len=max_len, packed=False, prefill_chunk=16,
                     hbm_cache_budget=budget), warm_prefix=False)
    paged_rep, paged_cap, paged_out = bench(
        EngineConfig(max_batch=16, max_len=max_len, packed=False,
                     prefill_chunk=16, hbm_cache_budget=budget,
                     paged=True, page_size=page_size), warm_prefix=True)

    total_prompt = sum(len(p) for p in prompts)
    rows = [{
        "case": "kv-paged/unpaged",
        "kv_bits": 4, "requests": n_req, "shared_prefix_len": PROMPT_LEN,
        "logical_slot_capacity": unpaged_cap["slots"],
        "peak_live_slot_count": unpaged_cap["slots"],
        "logical_slot_multiplier": 1.0,
        "prompt_rows_computed": unpaged_rep["prefill_tokens"],
        "prefix_share_ratio": 0.0,
        "tokens_match": True,
    }, {
        "case": "kv-paged/paged",
        "kv_bits": 4, "requests": n_req, "shared_prefix_len": PROMPT_LEN,
        "logical_slot_capacity": paged_cap["slots"],
        "peak_live_slot_count": paged_cap["peak_live_slot_count"],
        "logical_slot_multiplier": round(
            paged_cap["peak_live_slot_count"] / unpaged_cap["slots"], 2),
        "prompt_rows_computed": paged_rep["prefill_tokens"],
        "prefix_share_ratio": round(
            paged_cap["prefix_hit_tokens"] / total_prompt, 3),
        "tokens_match": paged_out == unpaged_out,
        "num_pages": paged_cap["num_pages"],
        "bytes_per_page": paged_cap["page_bytes"],
        "cached_prefix_pages": paged_cap["cached_prefix_pages"],
        "cow_copies": paged_cap["cow_copies"],
    }]
    emit(rows, ["case", "kv_bits", "requests", "shared_prefix_len",
                "logical_slot_capacity", "peak_live_slot_count",
                "logical_slot_multiplier", "prompt_rows_computed",
                "prefix_share_ratio", "tokens_match"])
    return rows


def run_sharded(quick: bool = False):
    """Sharded-vs-single-device packed engine throughput (report-only).

    Both engines serve the same seeded requests through the packed path
    (w2a2, kv_bits=4); the sharded one on a ('data'=1, 'model'=N) mesh
    over every host device.  ``tokens_match`` records the tentpole
    invariant (token-for-token identical output, tests/test_shard_serving
    gates it); ``decode_tok_s_ratio_vs_single`` is informational.
    """
    from repro import configs
    from repro.core.quant import QuantConfig
    from repro.launch.mesh import make_serving_mesh
    from repro.models import lm
    from repro.serve.config import EngineConfig
    from repro.serve.engine import Metrics, Request, ServingEngine

    n_dev = len(jax.devices())
    if n_dev < 2:
        # no comparison possible — skip the engine build/compile entirely
        # (run_engine already measures single-device throughput) and leave
        # a note row so the BENCH json says why the comparison is absent
        rows = [{"engine": "single-device", "devices": 1,
                 "note": ("host has 1 device; force a mesh with XLA_FLAGS="
                          "--xla_force_host_platform_device_count=4")}]
        emit(rows, ["engine", "devices", "note"])
        return rows
    cfg = configs.get_config("stablelm-1.6b", reduced=True).replace(
        param_dtype="float32", compute_dtype="float32",
        quant=QuantConfig(enabled=True, w_bits=2, a_bits=2, kv_bits=4))
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    n_req = 2 if quick else 4
    prompt_len, new_tokens = 8, 4 if quick else 8
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
               for _ in range(n_req)]

    def bench(mesh):
        eng = ServingEngine(cfg, params, mesh=mesh, config=EngineConfig(
            max_batch=2, max_len=32, packed=True, prefill_chunk=8))
        eng.submit(Request(uid=10_000, prompt=prompts[0],
                           max_new_tokens=2))      # warmup: compile steps
        eng.run_to_completion()
        eng.metrics = Metrics()
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=p, max_new_tokens=new_tokens))
        outs = {r.uid: tuple(r.output) for r in eng.run_to_completion()}
        return eng.metrics.report(), outs

    single_rep, single_out = bench(None)
    shard_rep, shard_out = bench(make_serving_mesh(n_dev))
    rows = [{"engine": "single-device", "devices": 1,
             "prefill_tok_s": single_rep["prefill_tok_s"],
             "decode_tok_s": single_rep["decode_tok_s"],
             "decode_tok_s_ratio_vs_single": 1.0, "tokens_match": True},
            {"engine": f"model-parallel-{n_dev}", "devices": n_dev,
             "prefill_tok_s": shard_rep["prefill_tok_s"],
             "decode_tok_s": shard_rep["decode_tok_s"],
             "decode_tok_s_ratio_vs_single": round(
                 shard_rep["decode_tok_s"]
                 / max(single_rep["decode_tok_s"], 1e-9), 3),
             "tokens_match": shard_out == single_out}]
    emit(rows, ["engine", "devices", "prefill_tok_s", "decode_tok_s",
                "decode_tok_s_ratio_vs_single", "tokens_match"])
    return rows


def run_router(quick: bool = False):
    """Replica-fleet saturation: Router(replicas=1) vs Router(replicas=2)
    over the same seeded burst (report-only; module docstring caveat).

    The burst oversubscribes each replica's bounded queue so the fleet
    spillover engages; the 2-replica row shows the spill falling and the
    aggregated decode rate roughly doubling by construction of the fleet
    metric (summed per-replica rates; DESIGN.md §17).
    """
    from repro import configs
    from repro.core.quant import QuantConfig
    from repro.models import lm
    from repro.serve.config import EngineConfig
    from repro.serve.router import Router

    cfg = configs.get_config("stablelm-1.6b", reduced=True).replace(
        param_dtype="float32", compute_dtype="float32",
        quant=QuantConfig(enabled=False))
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    econf = EngineConfig(max_batch=2, max_len=32, packed=False,
                         prefill_chunk=8, max_queue=2)
    n_req = 4 if quick else 8
    prompt_len, new_tokens = 8, 4 if quick else 8
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
               for _ in range(n_req)]

    def bench(replicas):
        router = Router(cfg, params, config=econf, replicas=replicas)
        router.submit(prompts[0], max_new_tokens=2)     # warmup: compile
        router.run_to_completion()
        router.reset_metrics()
        for p in prompts:
            router.submit(p, max_new_tokens=new_tokens)
        router.run_to_completion()
        return router.metrics_report()["fleet"]

    single = bench(1)
    fleet = bench(2)
    rows = []
    for rep in (single, fleet):
        rows.append({
            "case": f"router/replicas-{rep['replicas']}",
            "replicas": rep["replicas"],
            "requests": n_req,
            "decode_tok_s": rep["decode_tok_s"],
            "ttft_p95_s": rep["ttft_s"]["p95"],
            "spilled": rep["spilled"],
            "decode_tok_s_ratio_vs_single": round(
                rep["decode_tok_s"]
                / max(single["decode_tok_s"], 1e-9), 3),
        })
    emit(rows, ["case", "replicas", "requests", "decode_tok_s",
                "ttft_p95_s", "spilled", "decode_tok_s_ratio_vs_single"])
    return rows


def run_speculative(quick: bool = False):
    """Speculative decoding vs plain decode, same run (DESIGN.md §19).

    Two rows on the float engine at a decode-heavy workload (short
    prompts, long generations): plain token-at-a-time decode, and the
    draft-k + verify-in-one-call loop.  The draft here IS the target
    (float params), so greedy acceptance is 1.0 and the measured
    ``speculative_speedup`` isolates the loop's structural win — 2
    launches per committed-window cycle instead of one launch per token
    — at a verified-identical output (``tokens_match``).  The row
    carries ``floor: {speculative_speedup: 1.5}``, the hard same-run
    acceptance bar compare.py enforces on every current run.

    A third, report-only row packs the target at W2A2 and drafts through
    the re-packed sub-byte draft tree (serve/speculative.DraftModel) —
    the full draft-repack path under the real packed kernels, with
    ``acceptance_rate`` showing the draft's fidelity.
    """
    from repro import configs
    from repro.core.quant import QuantConfig
    from repro.models import lm
    from repro.serve.config import EngineConfig
    from repro.serve.engine import Metrics, Request, ServingEngine

    k = 8
    prompt_len = 8
    # 1 prefill-pass token + a whole number of full (k+1)-token cycles,
    # so every measured cycle runs at full draft depth
    new_tokens = 1 + (3 if quick else 6) * (k + 1)
    n_req = 2
    base = configs.get_config("stablelm-1.6b", reduced=True).replace(
        param_dtype="float32", compute_dtype="float32")
    float_cfg = base.replace(quant=QuantConfig(enabled=False))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, base.vocab_size, prompt_len).astype(np.int32)
               for _ in range(n_req)]

    def bench(cfg, spec_k, packed):
        eng = ServingEngine(cfg, lm.init_params(jax.random.PRNGKey(0), cfg),
                            config=EngineConfig(
            max_batch=n_req, max_len=prompt_len + new_tokens + 2,
            packed=packed, prefill_chunk=8, speculative_k=spec_k))
        # warmup: compile prefill + decode (or draft + verify) steps
        eng.submit(Request(uid=10_000, prompt=prompts[0],
                           max_new_tokens=spec_k + 2))
        eng.run_to_completion()
        eng.metrics = Metrics()
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=p, max_new_tokens=new_tokens))
        outs = {r.uid: tuple(r.output) for r in eng.run_to_completion()}
        return eng.metrics.report(), outs

    plain_rep, plain_out = bench(float_cfg, 0, packed=False)
    spec_rep, spec_out = bench(float_cfg, k, packed=False)
    rows = [{
        "case": "speculative/plain-decode",
        "speculative_k": 0, "new_tokens": new_tokens,
        "decode_tok_s": plain_rep["decode_tok_s"],
    }, {
        "case": "speculative/draft-verify",
        "speculative_k": k, "new_tokens": new_tokens,
        "decode_tok_s": spec_rep["decode_tok_s"],
        "acceptance_rate": spec_rep["acceptance_rate"],
        "spec_cycles": spec_rep["spec_cycles"],
        "speculative_speedup": round(
            spec_rep["decode_tok_s"]
            / max(plain_rep["decode_tok_s"], 1e-9), 2),
        "tokens_match": spec_out == plain_out,
        "floor": {"speculative_speedup": 1.5},
    }]
    packed_plain_rep, _ = bench(base, 0, packed=True)
    packed_rep, _ = bench(base, k, packed=True)
    rows.append({
        "case": "speculative/packed-w2-draft",
        "speculative_k": k, "new_tokens": new_tokens,
        "draft_w_bits": base.quant.w_bits,
        "decode_tok_s": packed_rep["decode_tok_s"],
        "acceptance_rate": packed_rep["acceptance_rate"],
        "spec_cycles": packed_rep["spec_cycles"],
        "decode_tok_s_ratio_vs_plain": round(
            packed_rep["decode_tok_s"]
            / max(packed_plain_rep["decode_tok_s"], 1e-9), 3),
    })
    emit(rows, ["case", "speculative_k", "new_tokens", "decode_tok_s",
                "acceptance_rate", "spec_cycles", "speculative_speedup",
                "tokens_match"])
    return rows


def run(quick: bool = False):
    return {"linear": run_linear(quick),
            "engine": run_engine(quick),
            "kv_cache": run_kv_cache(quick),
            "attention_decode": run_attention_decode(quick),
            "paged": run_paged(quick),
            "sharded": run_sharded(quick),
            "router": run_router(quick),
            "speculative": run_speculative(quick)}


if __name__ == "__main__":
    run()
