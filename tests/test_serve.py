"""Serving correctness: prefill/decode consistency vs full forward, SWA ring
buffer, packed-vs-qat logits closeness, and the continuous-batching engine
(chunked prefill, ragged per-slot positions, sampling, backpressure —
DESIGN.md §12)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.quant import QuantConfig
from repro.launch import steps as steps_lib
from repro.models import lm
from repro.serve.config import EngineConfig


def float_cfg(name, **kw):
    cfg = configs.get_config(name, reduced=True)
    # capacity_factor high enough to be dropless: teacher-forced and
    # token-by-token paths then agree exactly (drops are a train-time
    # throughput trade-off, not a serving semantic)
    return cfg.replace(param_dtype="float32", compute_dtype="float32",
                       quant=QuantConfig(enabled=False),
                       capacity_factor=8.0, **kw)


def _decode_all(cfg, params, tokens, max_len):
    """Feed tokens one-by-one through the decode step; return last logits."""
    decode = steps_lib.make_decode_step(cfg)
    b, s = tokens.shape
    caches = lm.init_caches(cfg, b, max_len, dtype=jnp.float32)
    logits = None
    for t in range(s):
        batch = {"tokens": tokens[:, t:t + 1]}
        if cfg.mrope:
            pos = jnp.full((3, b, 1), t, jnp.int32)
            batch["positions3"] = pos
        logits, caches = decode(params, caches, batch, jnp.int32(t))
    return logits


@pytest.mark.parametrize("name", ["stablelm-1.6b", "granite-3-8b",
                                  "mixtral-8x7b", "xlstm-1.3b",
                                  "jamba-1.5-large-398b"])
def test_decode_matches_full_forward(name):
    """Token-by-token decode == teacher-forced forward on the last position.
    Covers KV cache (GQA), SWA ring buffer, mamba/mLSTM/sLSTM state."""
    cfg = float_cfg(name)
    rng = np.random.default_rng(0)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    s = 12
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, s)), jnp.int32)

    full_logits, _, _ = lm.forward(params, cfg, {"tokens": tokens})
    dec_logits = _decode_all(cfg, params, tokens, max_len=s + 2)
    np.testing.assert_allclose(np.asarray(dec_logits),
                               np.asarray(full_logits[:, -1]),
                               rtol=2e-3, atol=2e-3)


def test_swa_ring_buffer_bounded_and_correct():
    """With window w, decode logits match full forward even when the ring
    cache is much smaller than the sequence."""
    cfg = float_cfg("mixtral-8x7b").replace(sliding_window=6)
    rng = np.random.default_rng(1)
    params = lm.init_params(jax.random.PRNGKey(1), cfg)
    s = 17
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, s)), jnp.int32)
    full_logits, _, _ = lm.forward(params, cfg, {"tokens": tokens})
    dec_logits = _decode_all(cfg, params, tokens, max_len=64)
    caches = lm.init_caches(cfg, 1, 64, dtype=jnp.float32)
    assert caches[0]["attn"]["k"].shape[1] == 6  # ring bounded by window
    np.testing.assert_allclose(np.asarray(dec_logits),
                               np.asarray(full_logits[:, -1]),
                               rtol=2e-3, atol=2e-3)


def test_prefill_then_decode_continues_correctly():
    cfg = float_cfg("stablelm-1.6b")
    rng = np.random.default_rng(2)
    params = lm.init_params(jax.random.PRNGKey(2), cfg)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 10)), jnp.int32)
    prefill = steps_lib.make_prefill_step(cfg, max_len=16)
    decode = steps_lib.make_decode_step(cfg)
    last, caches = prefill(params, {"tokens": tokens[:, :8]})
    for t in (8, 9):
        last, caches = decode(params, caches, {"tokens": tokens[:, t:t + 1]},
                              jnp.int32(t))
    full, _, _ = lm.forward(params, cfg, {"tokens": tokens})
    np.testing.assert_allclose(np.asarray(last), np.asarray(full[:, -1]),
                               rtol=2e-3, atol=2e-3)


def test_encdec_decode_uses_cached_cross_kv():
    cfg = float_cfg("seamless-m4t-medium")
    rng = np.random.default_rng(3)
    params = lm.init_params(jax.random.PRNGKey(3), cfg)
    enc = jnp.asarray(rng.normal(size=(2, 6, cfg.frontend_dim)), jnp.float32)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 5)), jnp.int32)
    # teacher-forced full forward
    full, _, _ = lm.forward(params, cfg, {"tokens": tokens,
                                          "enc_embeds": enc})
    # prefill-style: encode once, decode token by token with cached cross-KV
    enc_out = lm.encode(params, cfg, enc)
    caches = lm.init_caches(cfg, 2, 8, dtype=jnp.float32)
    logits = None
    for t in range(5):
        logits, _, caches = lm.forward(
            params, cfg, {"tokens": tokens[:, t:t + 1],
                          "positions": jnp.full((2, 1), t, jnp.int32)},
            caches=caches, cache_index=jnp.int32(t),
            enc_out=enc_out if t == 0 else None)
    np.testing.assert_allclose(np.asarray(logits[:, -1]),
                               np.asarray(full[:, -1]), rtol=2e-3, atol=2e-3)


def test_packed_decode_close_to_qat_forward():
    """The deployed integer path approximates the QAT fake-quant numerics
    (exact on the shared lattice up to activation-quant differences)."""
    from repro.serve.prepare import prepare_serving_params
    cfg = configs.get_config("stablelm-1.6b", reduced=True).replace(
        param_dtype="float32", compute_dtype="float32",
        quant=QuantConfig(enabled=True, w_bits=3, a_bits=3))
    rng = np.random.default_rng(4)
    params = lm.init_params(jax.random.PRNGKey(4), cfg)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 6)), jnp.int32)
    qat_logits, _, _ = lm.forward(params, cfg, {"tokens": tokens},
                                  quant_mode="qat")
    sp = prepare_serving_params(params, cfg)
    dec = _decode_all(cfg, sp, tokens, max_len=8)
    ref = np.asarray(qat_logits[:, -1, :cfg.vocab_size])
    got = np.asarray(dec[:, :cfg.vocab_size])
    # integer path vs fake-quant path: same weights lattice, activations
    # quantized at different points -> close but not identical
    corr = np.corrcoef(ref.ravel(), got.ravel())[0, 1]
    assert corr > 0.98, corr


def test_serving_engine_continuous_batching():
    from repro.serve.engine import Request, ServingEngine
    cfg = float_cfg("stablelm-1.6b")
    params = lm.init_params(jax.random.PRNGKey(5), cfg)
    eng = ServingEngine(cfg, params, config=EngineConfig(
        max_batch=2, max_len=32, packed=False))
    rng = np.random.default_rng(6)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size, 4).astype(
                        np.int32),
                    max_new_tokens=3) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    done = eng.run_to_completion()
    assert len(done) == 3
    assert all(len(r.output) == 3 for r in done)


# ---------------------------------------------------------------------------
# Ragged continuous batching (per-slot positions, DESIGN.md §12)
# ---------------------------------------------------------------------------

def _assert_staggered_decode_matches_single(cfg, seed, lens=(9, 5),
                                            started=(0, 4), max_len=16):
    """Drive two slots at staggered offsets through the vector-cache_index
    decode step and assert each matches its single-sequence reference.

    Uses the eager step: exact-logits asserts through large jitted
    programs hit a transient XLA:CPU execution race under CI memory
    pressure (same executable + same inputs can differ across runs);
    eager is deterministic, traces the identical ragged-position code,
    and the jitted path is covered token-for-token by the engine
    staggered-admission tests."""
    rng = np.random.default_rng(seed)
    params = lm.init_params(jax.random.PRNGKey(seed), cfg)
    toks = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in lens]
    refs = [np.asarray(_decode_all(cfg, params, jnp.asarray(t[None]),
                                   max_len))[0]
            for t in toks]

    decode = steps_lib.make_decode_step(cfg)
    caches = lm.init_caches(cfg, 2, max_len, dtype=jnp.float32)
    pos = np.zeros(2, np.int32)
    last = {}
    for tick in range(started[1] + lens[1]):
        tokens = np.zeros((2, 1), np.int32)
        valid = np.zeros(2, np.int32)
        for s in range(2):
            tl = tick - started[s]
            if 0 <= tl < lens[s]:
                tokens[s, 0] = toks[s][tl]
                valid[s] = 1
        # jnp.array (copy) — pos is mutated in place below, and a
        # zero-copy asarray would alias the buffer the async step reads
        logits, caches = decode(params, caches,
                                {"tokens": jnp.array(tokens)},
                                jnp.array(pos), jnp.array(valid))
        for s in range(2):
            if valid[s]:
                pos[s] += 1
                if tick - started[s] == lens[s] - 1:
                    last[s] = np.asarray(logits[s])
    for s in range(2):
        np.testing.assert_allclose(last[s], refs[s], rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("name", ["stablelm-1.6b", "jamba-1.5-large-398b"])
def test_ragged_decode_matches_single_sequence(name):
    """Vector cache_index decode: two slots advanced at staggered offsets
    produce the same logits as each sequence decoded alone (regression for
    the old lockstep max(slot_pos) position hack)."""
    _assert_staggered_decode_matches_single(float_cfg(name), seed=8)


def test_ragged_decode_sliding_window_matches_single():
    """Same, over a sliding-window ring cache (exercises the batched
    ring-position masking and per-slot ring writes)."""
    cfg = float_cfg("mixtral-8x7b").replace(sliding_window=6)
    assert lm.init_caches(cfg, 2, 16, dtype=jnp.float32)[0]["attn"][
        "k"].shape[1] == 6                    # ring bounded by window
    _assert_staggered_decode_matches_single(cfg, seed=14)


def test_engine_sliding_window_forces_token_prefill():
    """Ring-cache archs clamp prefill_chunk to 1 (chunked windows would
    overwrite slots still visible to earlier in-window queries) and still
    match the single-request reference token-for-token."""
    from repro.serve.engine import Request, ServingEngine
    cfg = float_cfg("mixtral-8x7b").replace(sliding_window=8)
    params = lm.init_params(jax.random.PRNGKey(15), cfg)
    rng = np.random.default_rng(15)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 3, 7)]

    def run(max_batch):
        eng = ServingEngine(cfg, params, config=EngineConfig(
            max_batch=max_batch, max_len=32, packed=False,
            prefill_chunk=16))
        assert eng.prefill_chunk == 1
        for i, p in enumerate(prompts):
            assert eng.submit(Request(uid=i, prompt=p, max_new_tokens=4))
        return {r.uid: tuple(r.output) for r in eng.run_to_completion()}

    assert run(2) == run(1)


@pytest.mark.parametrize("name", ["stablelm-1.6b", "xlstm-1.3b"])
def test_chunked_prefill_step_matches_decode(name):
    """make_prefill_chunk_step over ragged [B, chunk] windows reproduces
    token-by-token decode logits (attention ring writes + recurrent-state
    gating for pad tokens)."""
    cfg = float_cfg(name)
    rng = np.random.default_rng(9)
    params = lm.init_params(jax.random.PRNGKey(9), cfg)
    lens = np.asarray((11, 6))
    toks = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in lens]
    refs = [np.asarray(_decode_all(cfg, params, jnp.asarray(t[None]), 16))[0]
            for t in toks]

    pstep = steps_lib.make_prefill_chunk_step(cfg)  # eager: see ragged test
    caches = lm.init_caches(cfg, 2, 16, dtype=jnp.float32)
    pos = np.zeros(2, np.int32)
    fed = np.zeros(2, np.int32)
    chunk, last = 4, {}
    while (fed < lens).any():
        tokens = np.zeros((2, chunk), np.int32)
        valid = np.zeros(2, np.int32)
        for s in range(2):
            t = min(chunk, int(lens[s] - fed[s]))
            if t > 0:
                tokens[s, :t] = toks[s][fed[s]:fed[s] + t]
                valid[s] = t
        logits, caches = pstep(params, caches,
                               {"tokens": jnp.array(tokens)},
                               jnp.array(pos), jnp.array(valid))
        for s in range(2):
            if valid[s]:
                fed[s] += valid[s]
                pos[s] += valid[s]
                if fed[s] == lens[s]:
                    last[s] = np.asarray(logits[s])
    for s in range(2):
        np.testing.assert_allclose(last[s], refs[s], rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("chunk", [1, 4])
def test_engine_staggered_admission_matches_single_request(chunk):
    """The ragged-position regression test: four prompts of different
    lengths through a 3-slot engine (admissions land at staggered, per-slot
    positions; one request is admitted mid-flight into a freed slot) must
    generate token-for-token what a single-request engine generates."""
    from repro.serve.engine import Request, ServingEngine
    cfg = float_cfg("stablelm-1.6b")
    params = lm.init_params(jax.random.PRNGKey(5), cfg)
    rng = np.random.default_rng(10)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (7, 3, 11, 5)]

    def run(max_batch):
        eng = ServingEngine(cfg, params, config=EngineConfig(
            max_batch=max_batch, max_len=32, packed=False,
            prefill_chunk=chunk))
        for i, p in enumerate(prompts):
            assert eng.submit(Request(uid=i, prompt=p, max_new_tokens=6))
        return {r.uid: tuple(r.output) for r in eng.run_to_completion()}

    staggered = run(3)
    sequential = run(1)
    assert staggered == sequential


@pytest.mark.parametrize("kv_bits", [4, 2])
def test_engine_tokens_independent_of_batch_mates(kv_bits):
    """On the deployed path (packed kernels, paged sub-byte cache) a
    request's greedy tokens do not depend on what it shares steps with:
    prompts of mixed lengths served together equal the same prompts
    served one at a time — what a replica fleet relies on to match one
    engine.  Decode-phase slots step in the decode program while others
    prefill, so each row is always computed by the same program (on a
    TPU the two programs read the cache with different kernels)."""
    from repro.serve.engine import Request, ServingEngine

    cfg = configs.get_config("stablelm-1.6b", reduced=True)
    cfg = cfg.replace(quant=cfg.quant.replace(kv_bits=kv_bits))
    params = lm.init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(20 + kv_bits)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (4, 29, 13, 22)]
    eng = ServingEngine(cfg, params, config=EngineConfig(
        max_batch=4, max_len=40, paged=True, prefill_chunk=8))

    def serve(batch):
        for i in batch:
            assert eng.submit(Request(uid=i, prompt=prompts[i],
                                      max_new_tokens=8))
        return {r.uid: tuple(r.output) for r in eng.run_to_completion()}

    together = serve(range(len(prompts)))
    alone = {}
    for i in range(len(prompts)):
        alone.update(serve([i]))
    assert together == alone


def test_run_to_completion_collects_same_step_finishers():
    """A request with max_new_tokens=1 whose whole prompt fits one prefill
    chunk is admitted, prefilled, and retired inside a single step(); the
    old before-admission snapshot dropped it."""
    from repro.serve.engine import Request, ServingEngine
    cfg = float_cfg("stablelm-1.6b")
    params = lm.init_params(jax.random.PRNGKey(5), cfg)
    rng = np.random.default_rng(11)
    eng = ServingEngine(cfg, params, config=EngineConfig(
        max_batch=2, max_len=32, packed=False, prefill_chunk=8))
    for i in range(3):
        eng.submit(Request(
            uid=i, prompt=rng.integers(0, cfg.vocab_size, 3).astype(
                np.int32),
            max_new_tokens=1))
    done = eng.run_to_completion()
    assert len(done) == 3
    assert all(r.done and len(r.output) == 1 for r in done)


def test_engine_per_slot_sampling():
    """Greedy and temperature/top-k requests coexist in one batch; sampled
    slots are reproducible (seeded) and don't perturb greedy slots."""
    from repro.serve.engine import Request, SamplingParams, ServingEngine
    cfg = float_cfg("stablelm-1.6b")
    params = lm.init_params(jax.random.PRNGKey(5), cfg)
    rng = np.random.default_rng(12)
    p0 = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
    p1 = rng.integers(0, cfg.vocab_size, 4).astype(np.int32)

    def run():
        eng = ServingEngine(cfg, params, config=EngineConfig(
            max_batch=2, max_len=32, packed=False, prefill_chunk=4))
        eng.submit(Request(uid=0, prompt=p0, max_new_tokens=5))
        eng.submit(Request(uid=1, prompt=p1, max_new_tokens=5,
                           sampling=SamplingParams(temperature=1.0,
                                                   top_k=5, seed=3)))
        return {r.uid: tuple(r.output) for r in eng.run_to_completion()}

    a, b = run(), run()
    assert a == b                                 # seeded => reproducible

    eng = ServingEngine(cfg, params, config=EngineConfig(
        max_batch=1, max_len=32, packed=False, prefill_chunk=4))
    eng.submit(Request(uid=0, prompt=p0, max_new_tokens=5))
    solo = eng.run_to_completion()[0]
    assert a[0] == tuple(solo.output)             # greedy slot unperturbed


def test_engine_backpressure_and_metrics():
    from repro.serve.engine import Request, ServingEngine
    cfg = float_cfg("stablelm-1.6b")
    params = lm.init_params(jax.random.PRNGKey(5), cfg)
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, cfg.vocab_size, 5).astype(np.int32)
               for _ in range(3)]
    eng = ServingEngine(cfg, params, config=EngineConfig(
        max_batch=1, max_len=32, packed=False, prefill_chunk=4,
        max_queue=2))
    assert eng.submit(Request(uid=0, prompt=prompts[0], max_new_tokens=2))
    assert eng.submit(Request(uid=1, prompt=prompts[1], max_new_tokens=2))
    assert not eng.submit(Request(uid=2, prompt=prompts[2],
                                  max_new_tokens=2))   # cap hit
    with pytest.raises(ValueError):                    # cache-capacity cap
        eng.submit(Request(
            uid=3, prompt=rng.integers(0, cfg.vocab_size, 30).astype(
                np.int32),
            max_new_tokens=16))
    done = eng.run_to_completion()
    assert len(done) == 2
    rep = eng.metrics.report()
    assert rep["rejected"] == 1
    assert rep["admitted"] == rep["retired"] == 2
    assert rep["prefill_tokens"] == 10                 # two 5-token prompts
    assert rep["generated_tokens"] == 4                # 2 reqs x 2 tokens
    # first token of each request is sampled inside a prefill pass; only
    # the second comes from a pure decode pass
    assert rep["decode_tokens"] == 2
    assert 0.0 < rep["occupancy"] <= 1.0
    assert rep["prefill_tok_s"] > 0 and rep["decode_tok_s"] > 0


def test_int8_kv_cache_decode_accuracy():
    """int8 KV cache (beyond-paper §Perf optimization) stays close to the
    full-precision decode path."""
    cfg = float_cfg("granite-3-8b")
    cfg = cfg.replace(quant=QuantConfig(enabled=False, kv_bits=8))
    rng = np.random.default_rng(7)
    params = lm.init_params(jax.random.PRNGKey(7), cfg)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 12)), jnp.int32)
    caches = lm.init_caches(cfg, 2, 16, dtype=jnp.float32)
    assert caches[0]["attn"]["k"].dtype == jnp.int8
    full, _, _ = lm.forward(params, cfg, {"tokens": tokens})
    dec = _decode_all(cfg, params, tokens, max_len=16)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full[:, -1]),
                               rtol=0.05, atol=0.05)


@pytest.mark.parametrize("kv_bits", [4, 2, 8])
def test_engine_first_token_matches_uncached_packed_forward(kv_bits):
    """The paged engine's first token (chunked prefill through the packed
    kernels and the quantized page pool) is the argmax of one uncached
    packed forward of the prompt alone, in the real bf16 compute dtype:
    the uncached forward attends to its K/V at the cache precision, and
    every float sum on the path runs in a fixed order, so chunking,
    paging and batching change no rounding."""
    from repro.serve.engine import Request, ServingEngine

    cfg = configs.get_config("stablelm-1.6b", reduced=True)
    cfg = cfg.replace(quant=cfg.quant.replace(kv_bits=kv_bits))
    params = lm.init_params(jax.random.PRNGKey(7), cfg)
    rng = np.random.default_rng(kv_bits)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 16, 37, 21, 30, 9)]
    eng = ServingEngine(cfg, params, config=EngineConfig(
        max_batch=3, max_len=48, paged=True, prefill_chunk=8))
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=1))
    first = {r.uid: r.output[0] for r in eng.run_to_completion()}
    # compiled like the serving steps (eager op-by-op XLA rounds
    # differently from any fused program)
    forward = jax.jit(
        lambda params, tokens: lm.forward(params, cfg, {"tokens": tokens},
                                          quant_mode="packed")[0],
        compiler_options=steps_lib.SERVING_XLA_OPTIONS)
    for i, p in enumerate(prompts):
        logits = forward(eng.params, jnp.asarray(p)[None])
        assert first[i] == int(jnp.argmax(logits[0, -1])), i
