"""Paged, prefix-sharing sub-byte KV cache (DESIGN.md §18).

PagePool bookkeeping (refcounts, all-or-nothing alloc, radix prefix
index, LRU leaf eviction, meta round-trip), the page-size/word-packing
divisibility rule, and the engine-level invariants: block-table decode is
token-for-token identical to the slot-contiguous cache across kv_bits, a
fixed HBM budget admits >= 2x the logical slots on a shared-prefix
workload, and Router drain/restore carries the warm prefix cache across
the checkpoint boundary.

The 4-device tensor-parallel identity test rides the `shard` CI lane
(forced multi-device CPU host) and skips below 4 devices; the wide
kv_bits sweep is `slow` (nightly).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.quant import QuantConfig
from repro.launch.mesh import make_serving_mesh
from repro.models import lm
from repro.serve import pages
from repro.serve.config import EngineConfig
from repro.serve.engine import Request, ServingEngine
from repro.serve.prepare import cache_bytes_per_slot
from repro.serve.router import Router


def kv_cfg(kv_bits=0, name="stablelm-1.6b", **kw):
    return configs.get_config(name, reduced=True).replace(
        param_dtype="float32", compute_dtype="float32",
        quant=QuantConfig(enabled=False, kv_bits=kv_bits), **kw)


@pytest.fixture(scope="module")
def tiny():
    cfg = kv_cfg(4)
    return cfg, lm.init_params(jax.random.PRNGKey(0), cfg)


# ---------------------------------------------------------------------------
# Page-size granularity (the sub-byte wrinkle)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits,gran", [(0, 1), (8, 1), (4, 8), (2, 16)])
def test_page_granularity(bits, gran):
    assert pages.page_granularity(bits) == gran


@pytest.mark.parametrize("ps,bits,ok", [
    (16, 0, True), (16, 8, True), (16, 4, True), (16, 2, True),
    (8, 4, True), (8, 2, False), (12, 4, False), (1, 0, True),
])
def test_validate_page_size(ps, bits, ok):
    if ok:
        pages.validate_page_size(ps, bits)
    else:
        with pytest.raises(ValueError, match="word-packing tail"):
            pages.validate_page_size(ps, bits)
    with pytest.raises(ValueError, match="page_size"):
        pages.validate_page_size(0, bits)


# ---------------------------------------------------------------------------
# PagePool: physical pages
# ---------------------------------------------------------------------------

def test_alloc_all_or_nothing_and_refcounts():
    pool = pages.PagePool(num_pages=4, page_size=4)
    got = pool.alloc(3)
    assert len(got) == 3 and all(pool.ref[p] == 1 for p in got)
    # all-or-nothing: a too-big request takes NOTHING
    before = pool.report()["free_pages"]
    assert pool.alloc(2) is None
    assert pool.report()["free_pages"] == before == 1

    p = got[0]
    pool.retain(p)
    assert pool.is_shared(p) and pool.ref[p] == 2
    pool.release(p)
    assert not pool.is_shared(p)
    pool.release(p)                       # ref 0 -> back on the free list
    assert pool.report()["free_pages"] == 2
    with pytest.raises(RuntimeError, match="over-released"):
        pool.release(p)


def test_pool_constructor_validates():
    with pytest.raises(ValueError, match="num_pages"):
        pages.PagePool(0, 4)
    with pytest.raises(ValueError, match="word-packing tail"):
        pages.PagePool(4, 4, kv_bits=2)   # 2-bit words hold 16 values


# ---------------------------------------------------------------------------
# PagePool: prefix index
# ---------------------------------------------------------------------------

def test_register_and_match_prefix_full_and_partial():
    pool = pages.PagePool(num_pages=8, page_size=4)
    toks = list(range(100, 110))          # 10 tokens: 2 full pages + tail 2
    held = pool.alloc(3)
    assert pool.register_prefix(toks, held) == 3
    assert all(pool.ref[p] == 2 and pool.is_immutable(p) for p in held)

    n, hits = pool.match_prefix(toks)
    assert n == 10
    assert hits == [(held[0], 4), (held[1], 4), (held[2], 2)]

    # divergence mid-page: common head of the second chunk only
    n, hits = pool.match_prefix(toks[:5] + [999] * 5)
    assert n == 5 and hits == [(held[0], 4), (held[1], 1)]

    # max_tokens caps the walk inside the first page
    n, hits = pool.match_prefix(toks, max_tokens=3)
    assert n == 3 and hits == [(held[0], 3)]

    # a second registration of the same tokens is a no-op (hash-consed)
    dup = pool.alloc(3)
    assert pool.register_prefix(toks, dup) == 0
    assert all(pool.ref[p] == 1 for p in dup)


def test_eviction_is_lru_and_leaf_only():
    pool = pages.PagePool(num_pages=2, page_size=2)
    (a, b) = pool.alloc(2)
    pool.register_prefix([1, 2, 3, 4], [a, b])   # chain: a -> b
    pool.release(a)
    pool.release(b)                       # index-only now (ref 1 each)
    assert pool.report() == pool.report()  # sanity: report is pure
    assert pool.report()["cached_prefix_pages"] == 2

    # pressure: must evict the LEAF (b) first even though a is older
    got = pool.alloc(1)
    assert got == [b]
    assert pool.evicted_pages == 1
    n, _ = pool.match_prefix([1, 2, 3, 4])
    assert n == 2                          # parent chunk still cached
    got2 = pool.alloc(1)                   # now the orphaned parent goes
    assert got2 == [a] and pool.evicted_pages == 2
    assert pool.match_prefix([1, 2]) == (0, [])

    # pages shared with a live slot are never eviction victims
    pool2 = pages.PagePool(num_pages=2, page_size=2)
    (c, d) = pool2.alloc(2)
    pool2.register_prefix([5, 6], [c])     # ref(c) == 2: slot + index
    assert pool2.alloc(1) is None


def test_lru_respects_match_touch():
    pool = pages.PagePool(num_pages=3, page_size=2)
    (a,) = pool.alloc(1)
    pool.register_prefix([1, 2], [a])
    (b,) = pool.alloc(1)
    pool.register_prefix([3, 4], [b])
    pool.release(a)
    pool.release(b)
    pool.match_prefix([1, 2])              # a becomes most-recently-used
    (c,) = pool.alloc(1)
    pool.release(c)                        # free page consumed and returned
    # next pressure eviction takes b, the least recently touched leaf
    pool.alloc(2)
    assert pool.match_prefix([1, 2])[0] == 2
    assert pool.match_prefix([3, 4])[0] == 0


def test_pool_meta_round_trip():
    pool = pages.PagePool(num_pages=6, page_size=8, kv_bits=4)
    held = pool.alloc(3)
    toks = list(range(18))                 # 2 full pages + tail 2
    pool.register_prefix(toks, held)
    pool.release(held[2])                  # tail leaf: index-only
    pool.prefix_hits, pool.prefix_hit_tokens, pool.cow_copies = 2, 9, 1

    clone = pages.PagePool.from_meta(pool.export_meta())
    assert clone.report() == pool.report()
    assert clone.match_prefix(toks) == pool.match_prefix(toks)
    assert list(clone._free) == list(pool._free)
    assert (clone.ref == pool.ref).all()
    # the clock resumes past every restored stamp: a fresh touch on the
    # restored index must win any subsequent LRU comparison
    clone.match_prefix(toks[:4])
    node = clone._node_of_page[held[0]]
    assert all(node.stamp >= n.stamp
               for n in clone._node_of_page.values())


def test_copy_page_copies_attn_leaves_only():
    caches = [{
        "attn": {"k": jnp.arange(12, dtype=jnp.int32).reshape(3, 2, 2),
                 "k_scale": jnp.ones((3, 2), jnp.bfloat16) * 2},
        "recurrent": {"state": jnp.zeros((2, 4))},
    }]
    out = pages.copy_page(caches, src=0, dst=2)
    np.testing.assert_array_equal(np.asarray(out[0]["attn"]["k"][2]),
                                  np.asarray(caches[0]["attn"]["k"][0]))
    np.testing.assert_array_equal(np.asarray(out[0]["attn"]["k"][1]),
                                  np.asarray(caches[0]["attn"]["k"][1]))
    assert out[0]["recurrent"]["state"] is caches[0]["recurrent"]["state"]


# ---------------------------------------------------------------------------
# Engine: paged == unpaged, token for token
# ---------------------------------------------------------------------------

def shared_prefix_prompts(cfg, seed=11):
    """Prompt set exercising the whole sharing surface: full-page match,
    partial-tail match (COW on divergence), page-crossing prompts, and an
    unrelated request."""
    rng = np.random.default_rng(seed)
    base = rng.integers(0, cfg.vocab_size, 20).astype(np.int32)
    other = rng.integers(0, cfg.vocab_size, 5).astype(np.int32)
    return [
        base[:18],                          # registers 2 pages (16 + tail 2)
        np.concatenate([base[:16], other]),  # shares exactly page 0
        rng.integers(0, cfg.vocab_size, 7).astype(np.int32),   # no sharing
        base[:20],                          # partial-tail match -> COW
    ]


def run_engine(cfg, params, prompts, *, paged, mesh=None, max_new=4):
    eng = ServingEngine(cfg, params, mesh=mesh, config=EngineConfig(
        max_batch=2, max_len=48, packed=False, prefill_chunk=8,
        paged=paged, page_size=16))
    for i, p in enumerate(prompts):
        assert eng.submit(Request(uid=i, prompt=p, max_new_tokens=max_new))
    out = {r.uid: tuple(r.output) for r in eng.run_to_completion()}
    return out, eng


@pytest.mark.parametrize("kv_bits", [0, 4, 2])
def test_paged_identity_across_kv_bits(kv_bits):
    """The acceptance bar: block-table indirection is invisible in the
    tokens at bf16 and both sub-byte widths, while prefix hits and COW
    actually fire along the way."""
    cfg = kv_cfg(kv_bits)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompts = shared_prefix_prompts(cfg)
    want, _ = run_engine(cfg, params, prompts, paged=False)
    got, eng = run_engine(cfg, params, prompts, paged=True)
    assert got == want
    rep = eng.capacity_report()
    assert rep["paged"] and rep["prefix_sharing"]
    assert rep["prefix_hit_tokens"] >= 16    # page-0 reuse at minimum
    assert rep["cow_copies"] >= 1            # partial-tail divergence
    assert rep["pages_per_slot"] == 3        # ceil(48 / 16)


def test_paged_identity_without_sharing():
    """prefix_sharing=False still pages (pure indirection, no radix index)
    and still matches the unpaged engine."""
    cfg = kv_cfg(4)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompts = shared_prefix_prompts(cfg)
    want, _ = run_engine(cfg, params, prompts, paged=False)
    eng = ServingEngine(cfg, params, config=EngineConfig(
        max_batch=2, max_len=48, packed=False, prefill_chunk=8,
        paged=True, page_size=16, prefix_sharing=False))
    for i, p in enumerate(prompts):
        assert eng.submit(Request(uid=i, prompt=p, max_new_tokens=4))
    got = {r.uid: tuple(r.output) for r in eng.run_to_completion()}
    assert got == want
    rep = eng.capacity_report()
    assert not rep["prefix_sharing"] and rep["prefix_hit_tokens"] == 0


@pytest.mark.slow
@pytest.mark.parametrize("kv_bits", [0, 8, 4, 2])
def test_paged_identity_sweep_nightly(kv_bits):
    """Nightly-wide paged-vs-unpaged sweep: more requests than the pool
    holds at once, so admission backpressure, retirement recycling, and
    prefix-leaf eviction all run inside the identity check."""
    cfg = kv_cfg(kv_bits)
    params = lm.init_params(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(3)
    base = rng.integers(0, cfg.vocab_size, 32).astype(np.int32)
    prompts = [np.concatenate([base[:16 * (1 + i % 2)],
                               rng.integers(0, cfg.vocab_size, 3 + i)
                                  .astype(np.int32)])
               for i in range(6)]
    want, _ = run_engine(cfg, params, prompts, paged=False, max_new=6)
    got, eng = run_engine(cfg, params, prompts, paged=True, max_new=6)
    assert got == want
    assert eng.capacity_report()["prefix_hit_tokens"] > 0


needs_tp4 = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs 4 devices for a model=4 mesh "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


@pytest.mark.shard
@needs_tp4
def test_paged_identity_tensor_parallel():
    """Under a model=4 mesh the page pool's kv-head axis shards while the
    page axis replicates; tokens must still match the unpaged engine on
    the same mesh."""
    cfg = kv_cfg(4)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompts = shared_prefix_prompts(cfg)
    mesh = make_serving_mesh(4)
    want, _ = run_engine(cfg, params, prompts, paged=False, mesh=mesh)
    got, eng = run_engine(cfg, params, prompts, paged=True, mesh=mesh)
    assert got == want
    assert eng.capacity_report()["prefix_hit_tokens"] > 0


# ---------------------------------------------------------------------------
# Engine: capacity under a fixed budget
# ---------------------------------------------------------------------------

def test_paged_doubles_logical_slots_under_fixed_budget(tiny):
    """Same HBM budget, shared-prefix workload: the paged engine runs
    >= 2x the concurrent sequences the slot-contiguous engine can, with
    page-level accounting to show where the headroom came from."""
    cfg, params = tiny
    max_len, ps = 40, 8
    budget = 3 * cache_bytes_per_slot(cfg, max_len)
    unpaged = ServingEngine(cfg, params, config=EngineConfig(
        max_len=max_len, packed=False, prefill_chunk=8,
        hbm_cache_budget=budget))
    assert unpaged.max_batch == 3

    paged = ServingEngine(cfg, params, config=EngineConfig(
        max_batch=8, max_len=max_len, packed=False, prefill_chunk=8,
        hbm_cache_budget=budget, paged=True, page_size=ps))
    rep = paged.capacity_report()
    assert rep["num_pages"] == 15 and rep["pages_per_slot"] == 5
    assert rep["guaranteed_slots"] == 3     # worst case: no better than slots

    rng = np.random.default_rng(9)
    prefix = rng.integers(0, cfg.vocab_size, 24).astype(np.int32)
    # warm the radix cache: one request covering exactly the shared prefix
    assert paged.submit(Request(uid=99, prompt=prefix, max_new_tokens=1))
    paged.run_to_completion()
    assert paged.capacity_report()["cached_prefix_pages"] == 3
    paged.peak_live_slots = 0

    reqs = [Request(uid=i,
                    prompt=np.concatenate([prefix, [i]]).astype(np.int32),
                    max_new_tokens=2)
            for i in range(8)]
    for r in reqs:
        assert paged.submit(r)
    got = {r.uid: tuple(r.output) for r in paged.run_to_completion()}

    rep = paged.capacity_report()
    assert rep["peak_live_slot_count"] >= 2 * unpaged.max_batch
    assert rep["prefix_hits"] >= 8 and rep["prefix_hit_tokens"] >= 8 * 24

    for r in reqs:
        assert unpaged.submit(Request(uid=r.uid, prompt=r.prompt,
                                      max_new_tokens=2))
    want = {r.uid: tuple(r.output) for r in unpaged.run_to_completion()}
    assert got == want


def test_paged_rejects_incompatible_configs(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="sliding-window"):
        ServingEngine(cfg.replace(sliding_window=8), params,
                      config=EngineConfig(max_len=32, packed=False,
                                          paged=True))
    with pytest.raises(ValueError, match="word-packing tail"):
        ServingEngine(cfg, params, config=EngineConfig(
            max_len=32, packed=False, paged=True, page_size=4))
    xcfg = configs.get_config("xlstm-1.3b", reduced=True).replace(
        param_dtype="float32", compute_dtype="float32",
        quant=QuantConfig(enabled=False))
    xparams = lm.init_params(jax.random.PRNGKey(0), xcfg)
    with pytest.raises(ValueError, match="attention-free"):
        ServingEngine(xcfg, xparams, config=EngineConfig(
            max_len=32, packed=False, paged=True))


# ---------------------------------------------------------------------------
# Router: drain/restore carries the warm prefix cache
# ---------------------------------------------------------------------------

def test_paged_drain_restore_keeps_warm_prefix(tiny, tmp_path):
    """Drain -> checkpoint -> restore round-trips the page pools and the
    radix index: the restored replica still prefix-hits on the pre-drain
    prompt and serves token-identical output."""
    cfg, params = tiny
    econf = EngineConfig(max_batch=2, max_len=48, packed=False,
                         prefill_chunk=8, paged=True, page_size=16)
    prompts = shared_prefix_prompts(cfg)

    single = ServingEngine(cfg, params, config=econf)
    for i, p in enumerate(prompts):
        single.submit(Request(uid=i, prompt=p, max_new_tokens=4))
    want = {r.uid: tuple(r.output) for r in single.run_to_completion()}

    router = Router(cfg, params, config=econf, replicas=1,
                    checkpoint_dir=tmp_path)
    router.submit(prompts[0], max_new_tokens=4)
    router.run_to_completion()
    assert router.engines[0].capacity_report()["cached_prefix_pages"] == 2

    router.drain(0)
    router.restore(0)
    eng = router.engines[0]
    rep = eng.capacity_report()
    assert rep["cached_prefix_pages"] == 2   # the warm cache survived

    handles = [router.submit(p, max_new_tokens=4) for p in prompts]
    router.run_to_completion()
    assert {i: tuple(h.output) for i, h in enumerate(handles)} == want
    # prompts[0] resubmitted verbatim: its prefix must hit the restored
    # index without recomputation beyond the final row
    assert eng.capacity_report()["prefix_hit_tokens"] > 0


# ---------------------------------------------------------------------------
# The lane-flat pool: same stored words as the ring, same tokens
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_bits", [0, 8, 4, 2])
def test_lane_flat_pool_stores_the_ring_words(kv_bits):
    """The pool's value leaves are [P, page_size, KVH * w]: at every
    written (page, row, head) they hold the words (and the scale planes
    the scales) that the unpaged ring holds at the same absolute
    position, and paged greedy decode emits the ring's tokens."""
    from repro.models import attention

    cfg = kv_cfg(kv_bits)
    params = lm.init_params(jax.random.PRNGKey(2), cfg)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (21, 9)]
    engines = {}
    for paged in (False, True):
        eng = ServingEngine(cfg, params, config=EngineConfig(
            max_batch=2, max_len=48, packed=False, prefill_chunk=8,
            paged=paged, page_size=16, prefix_sharing=False))
        for i, p in enumerate(prompts):
            assert eng.submit(Request(uid=i, prompt=p, max_new_tokens=6))
        for _ in range(5):              # prefill chunks, then decodes
            eng.step()
        engines[paged] = eng
    ring, pool = engines[False], engines[True]
    np.testing.assert_array_equal(ring.slot_pos, pool.slot_pos)
    assert all(pool.slot_req) and pool.slot_pos.min() > 9

    ps, kvh = pool.page_size, cfg.num_kv_heads
    w = attention.row_width(cfg.resolved_head_dim, kv_bits)
    for ring_c, pool_c in zip(ring.caches, pool.caches):
        r, p = ring_c["attn"], pool_c["attn"]
        assert set(p) == set(r)
        assert p["k"].shape == (pool.num_pages, ps, kvh * w)
        for s, n in enumerate(pool.slot_pos):
            pos = np.arange(n)
            where = (pool.block_tables[s, pos // ps], pos % ps)
            for name, leaf in p.items():
                want = np.asarray(r[name])[s, pos]
                got = np.asarray(leaf)[where].reshape(want.shape)
                np.testing.assert_array_equal(got, want, err_msg=name)

    tokens = [{r.uid: tuple(r.output) for r in eng.run_to_completion()}
              for eng in (ring, pool)]
    assert tokens[0] == tokens[1] and len(tokens[0]) == 2


def test_decode_page_counters(tiny):
    """Metrics.decode_pages_live counts each decode launch's live table
    entries per live row, decode_pages_table its rows x table entries.
    Two requests served one after the other, pages of 16, 4 entries per
    row of 2: a 20-token prompt with 4 tokens out decodes at lengths 21,
    22, 23 (2 pages each), a 33-token prompt with 3 out at 34, 35 (3
    pages each)."""
    cfg, params = tiny
    eng = ServingEngine(cfg, params, config=EngineConfig(
        max_batch=2, max_len=64, packed=False, prefill_chunk=16,
        paged=True, page_size=16, prefix_sharing=False))
    rng = np.random.default_rng(5)
    for uid, (n, new) in enumerate([(20, 4), (33, 3)]):
        assert eng.submit(Request(uid=uid, prompt=rng.integers(
            0, cfg.vocab_size, n).astype(np.int32), max_new_tokens=new))
        eng.run_to_completion()
    m = eng.metrics
    assert m.decode_pages_live == 3 * 2 + 2 * 3
    assert m.decode_pages_table == 5 * 2 * 4
    assert m.report()["decode_live_page_share"] == round(12 / 40, 4)
