"""Fused sub-byte decode attention (DESIGN.md §20).

The flash-decoding read in kernels/ulppack_attention walks the stored —
possibly paged — cache in online-softmax groups: scores are computed on
the integer lattice (``scale * (q·u - zp·Σq)``), the running (m, l, acc)
carry replaces the full score row, and the paged variant indexes the
pool straight through the block table, so neither a dequantized KV view
nor the gathered logical view ever materializes.

Covered here: fused-vs-dense numerics for both registered backends
('xla' and 'pallas', the latter interpreted off-TPU) across kv_bits
{0, 8, 4, 2} x {contiguous, paged}; engine-level greedy token identity
against the legacy chunked path (the ``REPRO_FUSED_DECODE`` kill-switch
produces the reference); planner/autotuner plumbing; the
``_chunked_attention`` tail paths the fused route bypasses; and the
tensor-parallel identity on the forced-multi-device `shard` lane.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.quant import QuantConfig
from repro.kernels import autotune, plan as plan_lib, ulppack_attention
from repro.launch.mesh import make_serving_mesh
from repro.models import attention, lm
from repro.serve.config import EngineConfig
from repro.serve.engine import Request, ServingEngine


def kv_cfg(kv_bits=0, name="stablelm-1.6b", **kw):
    return configs.get_config(name, reduced=True).replace(
        param_dtype="float32", compute_dtype="float32",
        quant=QuantConfig(enabled=False, kv_bits=kv_bits), **kw)


# ---------------------------------------------------------------------------
# Numerics: fused read vs a dense dequantize-everything reference
# ---------------------------------------------------------------------------

def _make_cache(rng, b, s, kvh, hd, kv_bits):
    k = jnp.asarray(rng.standard_normal((b, s, kvh, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kvh, hd)), jnp.float32)
    if kv_bits in (8, 4, 2):
        qk, sk = attention._kv_quantize(k, kv_bits)
        qv, sv = attention._kv_quantize(v, kv_bits)
        return {"k": qk, "v": qv, "k_scale": sk, "v_scale": sv}
    return {"k": k, "v": v}


def _lane_flat(pool):
    """[P, ps, KVH, w] value leaves -> the stored pool [P, ps, KVH * w]
    (attention.init_paged_kv_cache); scale planes unchanged."""
    return {kk: vv.reshape(*vv.shape[:2], -1) for kk, vv in pool.items()}


def _dense_reference(q, cache, valid_len, qpos, kv_bits, hd):
    """Materialize the whole dequantized view; masked softmax; rows with
    nothing visible return exact zero (matching the fused l == 0 guard)."""
    if "k_scale" in cache:
        k = attention._kv_dequantize(cache["k"], cache["k_scale"],
                                     jnp.float32, kv_bits, hd)
        v = attention._kv_dequantize(cache["v"], cache["v_scale"],
                                     jnp.float32, kv_bits, hd)
    else:
        k, v = cache["k"], cache["v"]
    b, s, kvh, _ = k.shape
    _, c, h, _ = q.shape
    qg = (q.astype(jnp.float32) * hd ** -0.5).reshape(b, c, kvh,
                                                      h // kvh, hd)
    scores = jnp.einsum("bckgd,bskd->bckgs", qg, k.astype(jnp.float32))
    pos = jnp.arange(s)
    ok = (pos[None, None, :] < valid_len[:, None, None]) & \
         (pos[None, None, :] <= qpos[:, :, None])
    scores = jnp.where(ok[:, :, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where((~jnp.any(ok, axis=-1))[:, :, None, None, None],
                      0.0, probs)
    out = jnp.einsum("bckgs,bskd->bckgd", probs, v.astype(jnp.float32))
    return out.reshape(b, c, h, hd)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("paged", [False, True])
@pytest.mark.parametrize("kv_bits", [0, 8, 4, 2])
def test_fused_matches_dense_reference(kv_bits, paged, backend):
    rng = np.random.default_rng(kv_bits + 7 * paged)
    b, h, kvh, hd, c = 2, 4, 2, 16, 1
    if paged:
        ps, n_pages = 4, 8
        pool = _make_cache(rng, b * n_pages, ps, kvh, hd, kv_bits)
        bt = jnp.arange(b * n_pages, dtype=jnp.int32).reshape(b, n_pages)
        logical = {kk: vv.reshape(b, ps * n_pages, *vv.shape[2:])
                   for kk, vv in pool.items()}
        cache, s = _lane_flat(pool), ps * n_pages
    else:
        s = 32
        cache = _make_cache(rng, b, s, kvh, hd, kv_bits)
        bt, logical = None, cache
    q = jnp.asarray(rng.standard_normal((b, c, h, hd)), jnp.float32)
    valid_len = jnp.asarray([13, 7], jnp.int32)
    qpos = (valid_len[:, None] - c) + jnp.arange(c)[None, :]
    want = _dense_reference(q, logical, valid_len, qpos, kv_bits, hd)
    got = ulppack_attention.fused_decode_attention(
        q, cache, valid_len, qpos, kv_bits=kv_bits, hd=hd,
        block_tables=bt, backend=backend)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_fused_verify_window_and_dead_rows():
    """C > 1 (speculative-verify windows) routes through the fused path
    with per-position causal masking, and a valid_len == 0 row (dead
    engine slot) yields exact zeros instead of a uniform-softmax row."""
    rng = np.random.default_rng(5)
    b, h, kvh, hd, c, s = 2, 4, 2, 16, 3, 32
    cache = _make_cache(rng, b, s, kvh, hd, 2)
    q = jnp.asarray(rng.standard_normal((b, c, h, hd)), jnp.float32)
    valid_len = jnp.asarray([9, 0], jnp.int32)
    qpos = (valid_len[:, None] - c) + jnp.arange(c)[None, :]
    want = _dense_reference(q, cache, valid_len, qpos, 2, hd)
    for backend in ("xla", "pallas"):       # pallas re-routes C != 1
        got = ulppack_attention.fused_decode_attention(
            q, cache, valid_len, qpos, kv_bits=2, hd=hd, backend=backend)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got[1]), 0.0)


def test_fused_paged_scrambled_block_table():
    """A permuted (non-identity) block table reads the same tokens as the
    equivalently permuted contiguous cache — the in-kernel walk really
    follows the table, not physical order."""
    rng = np.random.default_rng(3)
    b, h, kvh, hd, ps, n_pages = 2, 4, 2, 16, 4, 6
    perm = rng.permutation(b * n_pages)
    pool = _make_cache(rng, b * n_pages, ps, kvh, hd, 4)
    bt = jnp.asarray(perm.reshape(b, n_pages), jnp.int32)
    logical = {kk: vv[perm].reshape(b, ps * n_pages, *vv.shape[2:])
               for kk, vv in pool.items()}
    q = jnp.asarray(rng.standard_normal((b, 1, h, hd)), jnp.float32)
    valid_len = jnp.asarray([ps * n_pages, 11], jnp.int32)
    qpos = valid_len[:, None] - 1
    want = _dense_reference(q, logical, valid_len, qpos, 4, hd)
    for backend in ("xla", "pallas"):
        got = ulppack_attention.fused_decode_attention(
            q, _lane_flat(pool), valid_len, qpos, kv_bits=4, hd=hd,
            block_tables=bt, backend=backend)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# The paged Pallas walk: several table entries per grid step, live pages
# copied from the pool, nothing else read
# ---------------------------------------------------------------------------

PS, KVH, HD, H = 4, 2, 16, 4


def _walk_case(case, kv_bits=4, seed=0):
    """(pool [P, PS, KVH, w], table, valid_len, chunks) of one walk case;
    the logical view of row r is pool[table[r]]."""
    rng = np.random.default_rng(seed)
    n_pages, chunks = 6, 2
    valid = {"partly_live": [7, 11],        # the last live step half full
             "dead_steps": [5, 2],          # steps 1-2 / 1-2 dead
             "empty_row": [13, 0, 9],       # no prefetch across row 1
             "ragged_table": [27, 9],       # 7 entries, steps of 2
             "scrambled": [24, 10]}[case]
    if case == "ragged_table":
        n_pages = 7
    b = len(valid)
    pool = _make_cache(rng, b * n_pages + 3, PS, KVH, HD, kv_bits)
    order = (rng.permutation(b * n_pages + 3)[:b * n_pages]
             if case == "scrambled" else np.arange(b * n_pages))
    bt = jnp.asarray(order.reshape(b, n_pages), jnp.int32)
    return pool, bt, jnp.asarray(valid, jnp.int32), chunks


def _walk_plan(chunks, interpret=True):
    return plan_lib.KernelPlan(
        op="attention_decode", backend="pallas", interpret=interpret,
        block_k=chunks * PS, chunks=chunks)


def _paged_and_contiguous(pool, bt, valid_len, chunks, kv_bits, seed=1):
    b = bt.shape[0]
    q = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (b, 1, H, HD)), jnp.float32)
    qpos = valid_len[:, None] - 1
    plan = _walk_plan(chunks)
    logical = {kk: vv[bt].reshape(b, -1, *vv.shape[2:])
               for kk, vv in pool.items()}
    paged = ulppack_attention.fused_decode_attention(
        q, _lane_flat(pool), valid_len, qpos, kv_bits=kv_bits, hd=HD,
        plan=plan, block_tables=bt)
    ring = ulppack_attention.fused_decode_attention(
        q, logical, valid_len, qpos, kv_bits=kv_bits, hd=HD, plan=plan)
    want = _dense_reference(q, logical, valid_len, qpos, kv_bits, HD)
    return paged, ring, want


@pytest.mark.parametrize("case", ["partly_live", "dead_steps", "empty_row",
                                  "ragged_table", "scrambled"])
@pytest.mark.parametrize("kv_bits", [0, 8, 4, 2])
def test_paged_walk_matches_reference_and_ring(case, kv_bits):
    """The paged kernel copies each step's live table entries and groups
    the online softmax by ``chunks`` pages: it agrees with the dense
    reference, and bit for bit with the contiguous read of the same
    logical view (the same ``block_k`` groups, the same shared math)."""
    pool, bt, valid_len, chunks = _walk_case(case, kv_bits)
    paged, ring, want = _paged_and_contiguous(pool, bt, valid_len, chunks,
                                              kv_bits)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(ring))
    if case == "empty_row":
        np.testing.assert_array_equal(np.asarray(paged[1]), 0.0)


@pytest.mark.parametrize("kv_bits", [4, 2])
def test_paged_walk_row_does_not_depend_on_batch_mates(kv_bits):
    """A row reads the same bits alone as beside other rows, whose pages
    the walk prefetches across the row boundary."""
    pool, bt, valid_len, chunks = _walk_case("empty_row", kv_bits)
    q = np.asarray(jax.random.normal(jax.random.PRNGKey(2),
                                     (bt.shape[0], 1, H, HD)))
    batch = ulppack_attention.fused_decode_attention(
        jnp.asarray(q), _lane_flat(pool), valid_len, valid_len[:, None] - 1,
        kv_bits=kv_bits, hd=HD, plan=_walk_plan(chunks), block_tables=bt)
    for r in range(bt.shape[0]):
        alone = ulppack_attention.fused_decode_attention(
            jnp.asarray(q[r:r + 1]), _lane_flat(pool), valid_len[r:r + 1],
            valid_len[r:r + 1, None] - 1, kv_bits=kv_bits, hd=HD,
            plan=_walk_plan(chunks), block_tables=bt[r:r + 1])
        np.testing.assert_array_equal(np.asarray(alone[0]),
                                      np.asarray(batch[r]))


@pytest.mark.parametrize("kv_bits", [8, 4, 2])
def test_paged_walk_reads_only_live_rows(kv_bits):
    """Poison every pool page that no live table entry names, and the dead
    rows of the live pages, with NaN scales and all-ones words: the output
    is finite and equal bit for bit to the unpoisoned run."""
    pool, bt, valid_len, chunks = _walk_case("scrambled", kv_bits)
    clean, _, _ = _paged_and_contiguous(pool, bt, valid_len, chunks, kv_bits)
    live = np.zeros(pool["k"].shape[:2], bool)      # [P, PS] rows read
    for row, n in zip(np.asarray(bt), np.asarray(valid_len)):
        for t in range(int(n)):
            live[row[t // PS], t % PS] = True
    dead = jnp.asarray(~live)
    poisoned = {
        kk: jnp.where(dead.reshape(*dead.shape, *([1] * (vv.ndim - 2))),
                      jnp.asarray(jnp.nan if kk.endswith("scale") else -1,
                                  vv.dtype), vv)
        for kk, vv in pool.items()}
    got, _, _ = _paged_and_contiguous(poisoned, bt, valid_len, chunks,
                                      kv_bits)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))


# ---------------------------------------------------------------------------
# Planner + autotuner plumbing
# ---------------------------------------------------------------------------

@pytest.fixture(autouse=True)
def _restore_active_cache():
    autotune.reset_active_cache()
    yield
    autotune.reset_active_cache()


def test_plan_attention_decode_page_rounding_and_budget():
    plan_lib.clear_plan_cache()
    p = plan_lib.plan_attention_decode(2, 256, 8, 4, 64, 2, page_size=16,
                                       backend="xla")
    assert p.block_k % 16 == 0 and p.chunks == p.block_k // 16
    # a starved budget halves block_k but never below one page
    q = plan_lib.plan_attention_decode(2, 256, 8, 4, 64, 2, page_size=16,
                                       backend="xla", vmem_budget=1)
    assert q.block_k == 16 and q.chunks == 1
    r = plan_lib.plan_attention_decode(2, 96, 8, 4, 64, 0, backend="xla")
    assert 1 <= r.block_k <= 96 and r.chunks == 1


def test_plan_attention_decode_consults_tuning_cache():
    cache = autotune.set_active_cache(autotune.TuningCache(device="cpu"))
    key = autotune.attention_decode_key(2, 128, 8, 4, 16, 2, page_size=8,
                                        backend="xla")
    autotune._store(cache, key, {"block_k": 24, "chunks": 3,
                                 "wall_us": 1.0})
    p = plan_lib.plan_attention_decode(2, 128, 8, 4, 16, 2, page_size=8,
                                       backend="xla")
    assert (p.block_k, p.chunks, p.source) == (24, 3, "tuned")
    autotune.reset_active_cache()
    p = plan_lib.plan_attention_decode(2, 128, 8, 4, 16, 2, page_size=8,
                                       backend="xla")
    assert p.source == "heuristic"


@pytest.mark.parametrize("paged", [False, True])
def test_tune_attention_decode_smoke(paged):
    cache = autotune.set_active_cache(autotune.TuningCache(device="cpu"))
    entry = autotune.tune_attention_decode(
        1, 32, 4, 2, 16, kv_bits=2, page_size=8 if paged else None,
        backend="xla", repeats=1)
    for field in ("block_k", "chunks", "wall_us", "heuristic_us",
                  "vmem_bytes", "candidates"):
        assert field in entry, field
    key = autotune.attention_decode_key(1, 32, 4, 2, 16, 2,
                                        page_size=8 if paged else None,
                                        backend="xla")
    assert cache.lookup(key) is entry
    plan = plan_lib.plan_attention_decode(
        1, 32, 4, 2, 16, 2, page_size=8 if paged else None, backend="xla")
    assert plan.source == "tuned" and plan.block_k == entry["block_k"]


# ---------------------------------------------------------------------------
# Engine-level greedy identity (the acceptance bar)
# ---------------------------------------------------------------------------

def _run_engine(cfg, params, prompts, *, paged, mesh=None, max_new=4):
    eng = ServingEngine(cfg, params, mesh=mesh, config=EngineConfig(
        max_batch=2, max_len=48, packed=False, prefill_chunk=8,
        paged=paged, page_size=16))
    for i, p in enumerate(prompts):
        assert eng.submit(Request(uid=i, prompt=p, max_new_tokens=max_new))
    return {r.uid: tuple(r.output) for r in eng.run_to_completion()}


def _prompts(cfg, seed=11):
    rng = np.random.default_rng(seed)
    base = rng.integers(0, cfg.vocab_size, 20).astype(np.int32)
    return [base[:18],
            rng.integers(0, cfg.vocab_size, 7).astype(np.int32),
            base[:20]]


@pytest.mark.parametrize("paged", [False, True])
@pytest.mark.parametrize("kv_bits", [0, 4, 2])
def test_engine_greedy_identity_fused_vs_legacy(kv_bits, paged):
    """Token-for-token: the fused decode read is invisible in the greedy
    tokens vs the legacy gather + chunked-softmax path (kill-switch off
    path produces the reference; distinct jit memo keys per §20)."""
    cfg = kv_cfg(kv_bits)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompts = _prompts(cfg)
    with ulppack_attention.disabled():
        want = _run_engine(cfg, params, prompts, paged=paged)
    got = _run_engine(cfg, params, prompts, paged=paged)
    assert got == want


needs_tp4 = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs 4 devices for a model=4 mesh "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


@pytest.mark.shard
@needs_tp4
def test_engine_greedy_identity_fused_tensor_parallel():
    """model=4 mesh: kv_shard_axis pins the 'xla' (GSPMD-partitionable)
    backend; tokens still match the legacy path on the same mesh."""
    cfg = kv_cfg(4)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompts = _prompts(cfg)
    mesh = make_serving_mesh(4)
    with ulppack_attention.disabled():
        want = _run_engine(cfg, params, prompts, paged=True, mesh=mesh)
    got = _run_engine(cfg, params, prompts, paged=True, mesh=mesh)
    assert got == want


# ---------------------------------------------------------------------------
# Legacy-path tails the fused route bypasses (kept load-bearing for
# prefill, windows, and non-fused fallbacks)
# ---------------------------------------------------------------------------

def _legacy_setup(rng, b, sq, skv, h, kvh, hd, kv_bits):
    q = jnp.asarray(rng.standard_normal((b, sq, h, hd)), jnp.float32)
    cache = _make_cache(rng, b, skv, kvh, hd, kv_bits)
    kv_fn = lambda: (attention._kv_dequantize(cache["k"], cache["k_scale"],
                                              jnp.float32, kv_bits, hd),
                     attention._kv_dequantize(cache["v"], cache["v_scale"],
                                              jnp.float32, kv_bits, hd))
    positions = jnp.broadcast_to(jnp.arange(sq)[None, :], (b, sq))

    def mask_fn(qpos):
        return jnp.arange(skv)[None, None, :] <= qpos[:, :, None]

    return q, kv_fn, mask_fn, positions


def test_chunked_attention_remainder_tail():
    """Sq % chunk != 0 exercises the `rem` tail chunk; result equals the
    single-chunk (chunk >= Sq) evaluation."""
    rng = np.random.default_rng(1)
    b, sq, skv, h, kvh, hd = 2, 7, 12, 4, 2, 16
    q, kv_fn, mask_fn, pos = _legacy_setup(rng, b, sq, skv, h, kvh, hd, 4)
    whole = attention._chunked_attention(q, kv_fn, mask_fn, pos, sq)
    tailed = attention._chunked_attention(q, kv_fn, mask_fn, pos, 3)
    np.testing.assert_allclose(np.asarray(tailed), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)


def test_chunked_attention_gqa_groups_quantized_kv():
    """GQA (H > KVH) with a 2-bit packed cache: grouped einsums agree with
    an explicit per-head evaluation that repeats each kv head."""
    rng = np.random.default_rng(2)
    b, sq, skv, h, kvh, hd = 2, 5, 16, 8, 2, 16
    q, kv_fn, mask_fn, pos = _legacy_setup(rng, b, sq, skv, h, kvh, hd, 2)
    got = attention._chunked_attention(q, kv_fn, mask_fn, pos, 2)
    k, v = kv_fn()
    rep = h // kvh
    kf = jnp.repeat(k, rep, axis=2)
    vf = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bshd->bhqs", q * hd ** -0.5, kf)
    scores = jnp.where(mask_fn(pos)[:, None, :, :], scores, -1e30)
    want = jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(scores, -1), vf)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
