"""Attention: GQA / MQA, sliding-window (ring-buffer KV), M-RoPE, cross-attn,
query-chunked exact softmax (flash-style memory behaviour in pure JAX).

Projections are quantizable Dense layers (the paper's technique applies to
them); the score/value einsums stay bf16 (DESIGN.md §5).  The decode KV
cache is additionally storable at int8 or sub-byte (bit-dense packed words,
cfg.quant.kv_bits; DESIGN.md §13) with unpack+dequant fused into the
q-chunked loop.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.models import common
from repro.models.common import dense_apply, dense_init

NEG_INF = -1e30


def attention_init(key, cfg, *, cross=False, dtype=jnp.float32):
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    q = dense_init(ks[0], cfg.d_model, cfg.num_heads * hd,
                   use_bias=cfg.qkv_bias, dtype=dtype,
                   quantized=True, qcfg=cfg.quant)
    k = dense_init(ks[1], cfg.d_model, cfg.num_kv_heads * hd,
                   use_bias=cfg.qkv_bias, dtype=dtype,
                   quantized=True, qcfg=cfg.quant)
    v = dense_init(ks[2], cfg.d_model, cfg.num_kv_heads * hd,
                   use_bias=cfg.qkv_bias, dtype=dtype,
                   quantized=True, qcfg=cfg.quant)
    o = dense_init(ks[3], cfg.num_heads * hd, cfg.d_model, dtype=dtype,
                   quantized=True, qcfg=cfg.quant,
                   scale=1.0 / (cfg.num_heads * hd) ** 0.5)
    p = {"q": q, "k": k, "v": v, "o": o}
    del cross
    return p


def init_kv_cache(cfg, batch, max_len, dtype=jnp.bfloat16):
    """Ring-buffer KV cache; SWA archs allocate only the window.

    ``cfg.quant.kv_bits`` selects the storage precision (DESIGN.md §13):
      0 / 16 — full ``dtype`` (bf16 in serving), the baseline.
      8      — int8 values + per-(pos, kv-head) bf16 absmax scales (~2x).
      4 / 2  — bit-dense int32 words (``packing.pack_words`` along head_dim,
               ``32 // kv_bits`` values per word, zero-padded tail) + the
               same per-(pos, kv-head) bf16 scales (~4x / ~8x).  The read
               path never materializes the full-precision cache: unpack +
               dequant are fused into the q-chunked attention loop.
    """
    hd = cfg.resolved_head_dim
    size = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    kvh = cfg.num_kv_heads
    bits = getattr(cfg.quant, "kv_bits", 0)
    if bits == 8:
        return {
            "k": jnp.zeros((batch, size, kvh, hd), jnp.int8),
            "v": jnp.zeros((batch, size, kvh, hd), jnp.int8),
            "k_scale": jnp.zeros((batch, size, kvh), jnp.bfloat16),
            "v_scale": jnp.zeros((batch, size, kvh), jnp.bfloat16),
        }
    if bits in (4, 2):
        hd_words = -(-hd // (32 // bits))
        return {
            "k": jnp.zeros((batch, size, kvh, hd_words), jnp.int32),
            "v": jnp.zeros((batch, size, kvh, hd_words), jnp.int32),
            "k_scale": jnp.zeros((batch, size, kvh), jnp.bfloat16),
            "v_scale": jnp.zeros((batch, size, kvh), jnp.bfloat16),
        }
    if bits not in (0, 16):
        raise ValueError(f"unsupported kv_bits {bits}; expected 0/16/8/4/2")
    return {
        "k": jnp.zeros((batch, size, kvh, hd), dtype),
        "v": jnp.zeros((batch, size, kvh, hd), dtype),
    }


def init_paged_kv_cache(cfg, num_pages, page_size, dtype=jnp.bfloat16):
    """Paged KV pool: ``num_pages`` pages of ``page_size`` token rows.

    Same per-row layouts as :func:`init_kv_cache` with the slot-contiguous
    ``[B, S, ...]`` leading dims replaced by ``[P, page_size, ...]`` — the
    kv-head axis stays axis 2, so the serving kv-head shardings
    (DESIGN.md §15) apply to pools unchanged while the page axis
    replicates.  One page-id space serves every attention layer: layer
    ``i``'s pool is indexed by the same block tables (serve/pages.py).
    Sub-byte layouts additionally require ``page_size`` to be a multiple
    of the word-packing tail (serve/pages.validate_page_size) so each
    page holds whole int32 words and dequantizes independently; the
    per-(pos, kv-head) scale planes page alongside the words.

    Sliding-window archs keep the unpaged ring (ring slot reuse and page
    indirection do not compose; the engine rejects the combination).
    """
    if cfg.sliding_window:
        raise ValueError(
            "paged KV cache does not support sliding-window ring caches; "
            "serve sliding-window archs unpaged")
    hd = cfg.resolved_head_dim
    kvh = cfg.num_kv_heads
    bits = getattr(cfg.quant, "kv_bits", 0)
    if bits == 8:
        return {
            "k": jnp.zeros((num_pages, page_size, kvh, hd), jnp.int8),
            "v": jnp.zeros((num_pages, page_size, kvh, hd), jnp.int8),
            "k_scale": jnp.zeros((num_pages, page_size, kvh), jnp.bfloat16),
            "v_scale": jnp.zeros((num_pages, page_size, kvh), jnp.bfloat16),
        }
    if bits in (4, 2):
        hd_words = -(-hd // (32 // bits))
        return {
            "k": jnp.zeros((num_pages, page_size, kvh, hd_words), jnp.int32),
            "v": jnp.zeros((num_pages, page_size, kvh, hd_words), jnp.int32),
            "k_scale": jnp.zeros((num_pages, page_size, kvh), jnp.bfloat16),
            "v_scale": jnp.zeros((num_pages, page_size, kvh), jnp.bfloat16),
        }
    if bits not in (0, 16):
        raise ValueError(f"unsupported kv_bits {bits}; expected 0/16/8/4/2")
    return {
        "k": jnp.zeros((num_pages, page_size, kvh, hd), dtype),
        "v": jnp.zeros((num_pages, page_size, kvh, hd), dtype),
    }


def _kv_quantize(x, bits=8):
    """[B,S,KVH,hd] float -> (stored lattice, bf16 per-(pos,head) scales).

    bits == 8: signed int8 absmax (the legacy layout).  bits in (4, 2):
    midpoint-zero-point unsigned lattice — scale targets ``qmax - zp`` steps
    (the calibrate_absmax convention) so +amax hits exactly ``qmax`` — packed
    bit-dense along head_dim into int32 words.  The 1e-8 scale floor keeps
    all-zero rows (untouched cache slots, zero projections) NaN-free.
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    if bits == 8:
        scale = jnp.maximum(amax / 127.0, 1e-8)
        q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                     -127, 127).astype(jnp.int8)
        return q, scale.astype(jnp.bfloat16)
    zp = 1 << (bits - 1)
    qmax = (1 << bits) - 1
    scale = jnp.maximum(amax / (qmax - zp), 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]) + zp,
                 0, qmax).astype(jnp.int32)
    return packing.pack_words(q, bits, axis=-1), scale.astype(jnp.bfloat16)


def _kv_dequantize(q, scale, dtype=jnp.float32, bits=8, hd=None):
    # compute in the target dtype: the lattice values are exact in bf16, and
    # f32 intermediates here would double the dominant decode traffic (§Perf)
    if bits == 8:
        return q.astype(dtype) * scale.astype(dtype)[..., None]
    zp = 1 << (bits - 1)
    vals = packing.unpack_words(q, bits, hd, axis=-1)
    return (vals.astype(dtype) - zp) * scale.astype(dtype)[..., None]


def _chunked_attention(q, kv_fn, mask_fn, q_positions, chunk: int):
    """Exact softmax attention, q-chunked to bound the score buffer.

    q: [B, Sq, H, hd]; kv_fn() -> (k, v) each [B, Sk, KVH, hd] — invoked
    INSIDE the chunk body so a quantized/bit-packed KV cache is expanded
    (unpack + dequant) per chunk in registers/VMEM and fused into the score
    and value einsums, never materialized at full precision across the whole
    call; mask_fn(qpos[chunk]) -> [B, chunk, Sk] boolean validity.
    Returns [B, Sq, H, hd].
    """
    b, sq, h, hd = q.shape
    scale = hd ** -0.5
    # operands stay in their storage dtype (bf16 on TPU) with f32 MXU
    # accumulation — avoids materializing f32 copies of the whole KV cache
    # (§Perf cell-C iteration 2: the f32 upcast was 2x the cache traffic)
    opd = q.dtype

    def one_chunk(qc, qpos):
        # qc: [B, C, H, hd]
        k, v = kv_fn()
        kvh = k.shape[2]
        groups = h // kvh
        qg = (qc.astype(jnp.float32) * scale).astype(opd)
        qg = qg.reshape(b, qc.shape[1], kvh, groups, hd)
        scores = jnp.einsum("bckgd,bskd->bckgs", qg, k.astype(opd),
                            preferred_element_type=jnp.float32)
        valid = mask_fn(qpos)[:, :, None, None, :]        # [B,C,1,1,Sk]
        scores = jnp.where(valid, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bckgs,bskd->bckgd", probs.astype(opd),
                         v.astype(opd),
                         preferred_element_type=jnp.float32)
        return out.reshape(b, qc.shape[1], h, hd)

    if sq <= chunk:
        return one_chunk(q, q_positions).astype(q.dtype)
    # per-chunk remat: backward recomputes the [C, Sk] score block instead of
    # storing scores+probs for every chunk (flash-style memory behaviour)
    chunk_fn = jax.checkpoint(lambda args: one_chunk(*args))
    n = sq // chunk
    rem = sq - n * chunk
    qs = jnp.moveaxis(
        q[:, :n * chunk].reshape(b, n, chunk, h, hd), 1, 0)
    ps = jnp.moveaxis(
        q_positions[:, :n * chunk].reshape(b, n, chunk), 1, 0)
    outs = jax.lax.map(chunk_fn, (qs, ps))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, n * chunk, h, hd)
    if rem:
        tail = one_chunk(q[:, n * chunk:], q_positions[:, n * chunk:])
        out = jnp.concatenate([out, tail], axis=1)
    return out.astype(q.dtype)


def precompute_cross_kv(p, cfg, enc_out, *, quant_mode="none"):
    """Project encoder states to K/V once (reused every decode step)."""
    b = enc_out.shape[0]
    hd = cfg.resolved_head_dim
    cd = common.dtype_of(cfg.compute_dtype)
    qm = dict(qcfg=cfg.quant, quant_mode=quant_mode, compute_dtype=cd)
    k = dense_apply(p["k"], enc_out, **qm).reshape(b, -1, cfg.num_kv_heads,
                                                   hd)
    v = dense_apply(p["v"], enc_out, **qm).reshape(b, -1, cfg.num_kv_heads,
                                                   hd)
    return k, v


def _constrain_kv_heads(tree, axis):
    """Pin cache-layout tensors to the serving kv-head shard axis.

    ``axis`` is the mesh axis the serving ShardPlan sharded the kv-head
    dim over (DESIGN.md §15); the constraint keeps the quantize -> pack ->
    scatter write chain head-local so GSPMD neither gathers the incoming
    [B, s, KVH, hd] slice nor reshards the ring between steps.  Applies to
    K/V (and packed-word) tensors [B, S, KVH, hd|words] and the
    per-(pos, kv-head) scale planes [B, S, KVH]; no-op when ``axis`` is
    None or outside a mesh context (sharding.constrain guards)."""
    if axis is None:
        return tree
    from repro.parallel.sharding import constrain

    def one(t):
        if t.ndim == 4:
            return constrain(t, None, None, axis, None)
        if t.ndim == 3:
            return constrain(t, None, None, axis)
        return t

    if isinstance(tree, dict):
        return {k: one(v) for k, v in tree.items()}
    return one(tree)


def _fused_decode_epilogue(p, cfg, q, read_cache, valid_len, positions,
                           kv_bits, new_cache, qm, kv_shard_axis,
                           block_tables=None):
    """Decode tail via the fused flash-decoding read (DESIGN.md §20):
    kernels/ulppack_attention walks the stored — possibly paged — cache in
    online-softmax groups, so neither the dequantized view, the gathered
    paged view, nor a full score block materializes.  ``valid_len`` [B] is
    each row's live logical-view prefix; the group mask
    ``pos < valid_len & pos <= qpos`` is exactly the legacy
    ``_ring_positions*`` visibility for non-windowed caches.  Under
    sharded serving (``kv_shard_axis``) GSPMD partitions the 'xla'
    backend, and the Pallas kernel runs per kv-head shard under
    shard_map."""
    from repro.kernels import ulppack_attention

    b, sq, h, hd = q.shape
    with jax.named_scope("core"):
        if positions.ndim == 1:
            positions = jnp.broadcast_to(positions[None, :], (b, sq))
        out = ulppack_attention.fused_decode_attention(
            q, read_cache, valid_len, positions, kv_bits=kv_bits, hd=hd,
            block_tables=block_tables, shard_axis=kv_shard_axis)
    with jax.named_scope("out"):
        out = dense_apply(p["o"], out.reshape(b, sq, h * hd), **qm)
    return out, new_cache


def _use_fused_decode(window, kv_x, idx, sq) -> bool:
    """Trace-time gate for the fused decode read: self-attention decode
    over a non-windowed cache (sliding-window rings keep the legacy ring-
    position mask; scalar lockstep callers beyond one token predate the
    per-row valid_len semantics)."""
    from repro.kernels import ulppack_attention

    if not ulppack_attention.enabled() or window or kv_x is not None:
        return False
    return idx.ndim > 0 or sq == 1


def _attention_epilogue(p, cfg, q, kv_fn, mask_fn, positions, q_chunk,
                        skv, kv_bits, new_cache, qm):
    """Shared attention tail: positions broadcast, autotuned q-chunk
    lookup, the q-chunked softmax, and the output projection."""
    b, sq, h, hd = q.shape
    with jax.named_scope("core"):
        if positions.ndim == 1:
            positions = jnp.broadcast_to(positions[None, :], (b, sq))
        if q_chunk is None:
            from repro.kernels import autotune  # trace-time lookup
            q_chunk = autotune.attention_chunk_for(
                b, sq, int(skv), cfg.num_heads, cfg.num_kv_heads, hd,
                int(kv_bits))
        out = _chunked_attention(q, kv_fn, mask_fn, positions, q_chunk)
    with jax.named_scope("out"):
        out = dense_apply(p["o"], out.reshape(b, sq, h * hd), **qm)
    return out, new_cache


def attention_apply(p, cfg, x, *, positions, quant_mode="none",
                    cache=None, cache_index=None, cache_valid=None,
                    kv_x=None, kv_positions=None, causal=True,
                    positions3=None, q_chunk=None, cross_kv=None,
                    kv_shard_axis=None, block_tables=None):
    """Full attention forward.

    ``q_chunk=None`` consults the autotune cache for the fused-attention
    chunk tuned for this (batch, q-len, kv-len, heads, head-dim, kv_bits)
    signature (kernels/autotune.py), falling back to 512; pass an int to
    pin it.

    Modes:
      * training/prefill: cache=None (or cache provided to be FILLED when
        cache_index is None -> returns (out, new_cache)).
      * decode: cache + cache_index given, x is [B, 1, d].  A scalar
        cache_index is the lockstep path (all rows share one position); a
        [B] vector gives each row its own write offset (ragged batches,
        DESIGN.md §12), with x [B, S, d] for chunked prefill.
      * ragged windows: cache_valid [B] counts the valid-prefix tokens of
        each row's window; trailing pad tokens are never written to the
        cache (0 = dead slot, fully masked).
      * cross-attention: kv_x (encoder states) given; non-causal, no RoPE
        ring-buffer concerns.
      * paged decode: ``block_tables`` [B, n_pages] maps each row's
        logical page j to a physical page of a pooled cache
        ([P, page_size, KVH, ...], init_paged_kv_cache).  Writes scatter
        through the table; reads gather the row's pages back into a
        logical [B, n_pages*page_size, ...] view INSIDE the q-chunk body,
        so fused sub-byte dequant is preserved bit-exactly (positions the
        mask admits hold values identical to the unpaged ring, and masked
        rows contribute exactly-zero probability).  Vector cache_index
        only; sliding-window archs stay unpaged (DESIGN.md §18).

    Device scopes (``jax.named_scope``): ``qkv`` (projections, RoPE),
    ``kv_write`` (quantize, pack and write into the cache or pool),
    ``core`` (the attention read) and ``out`` (the output projection).
    """
    b, sq, _ = x.shape
    hd = cfg.resolved_head_dim
    cd = common.dtype_of(cfg.compute_dtype)
    qm = dict(qcfg=cfg.quant, quant_mode=quant_mode, compute_dtype=cd)

    with jax.named_scope("qkv"):
        q = dense_apply(p["q"], x, **qm).reshape(b, sq, cfg.num_heads, hd)
        if cross_kv is not None:
            k, v = cross_kv
            kv_x = True  # marks cross-attention masking below
        else:
            kv_in = kv_x if kv_x is not None else x
            k = dense_apply(p["k"], kv_in, **qm).reshape(
                b, -1, cfg.num_kv_heads, hd)
            v = dense_apply(p["v"], kv_in, **qm).reshape(
                b, -1, cfg.num_kv_heads, hd)

        if kv_x is None:  # self-attention: rotate q and k
            if cfg.mrope and positions3 is not None:
                q = common.apply_mrope(q, positions3, cfg.mrope_sections,
                                       cfg.rope_theta)
                k = common.apply_mrope(k, positions3, cfg.mrope_sections,
                                       cfg.rope_theta)
            else:
                q = common.apply_rope(q, positions, cfg.rope_theta)
                k = common.apply_rope(k, positions, cfg.rope_theta)

    window = cfg.sliding_window
    kv_bits = getattr(cfg.quant, "kv_bits", 0)
    new_cache = None

    if cache is not None and cache_index is not None:
        # ---- decode / chunked prefill: write new k/v into the ring ----
        # under serving TP the incoming slice and the written ring stay
        # pinned to the kv-head shard axis (no-op when axis is None)
        k = _constrain_kv_heads(k, kv_shard_axis)
        v = _constrain_kv_heads(v, kv_shard_axis)
        idx = jnp.asarray(cache_index)
        if block_tables is not None:
            # ---- paged pool: scatter/gather through the block table ----
            if window:
                raise NotImplementedError(
                    "paged KV cache + sliding-window ring do not compose; "
                    "serve sliding-window archs unpaged")
            if idx.ndim == 0:
                raise NotImplementedError(
                    "paged decode is vector-indexed (per-slot positions); "
                    "pass cache_index as a [B] array")
            bt = jnp.asarray(block_tables, jnp.int32)
            page_rows = cache["k"].shape[1]
            size = bt.shape[1] * page_rows     # logical view length
            vlen = (jnp.full((b,), sq, jnp.int32) if cache_valid is None
                    else jnp.asarray(cache_valid, jnp.int32))
            offs = jnp.arange(sq, dtype=jnp.int32)
            wpos = idx[:, None] + offs[None, :]                # [B, sq]
            with jax.named_scope("kv_write"):
                page_idx = jnp.clip(wpos // page_rows, 0, bt.shape[1] - 1)
                phys = jnp.take_along_axis(bt, page_idx, axis=1)
                new_cache = _cache_write_paged(
                    cache, k, v, phys, wpos % page_rows,
                    offs[None, :] < vlen[:, None], kv_bits)
            # logical row j of the gathered view holds absolute position
            # j by construction (page j // page_rows, row j % page_rows),
            # so the unpaged no-window position map applies verbatim
            kv_pos = _ring_positions_batch(idx + vlen - 1, size,
                                           0)                  # [B, size]
            new_cache = _constrain_kv_heads(new_cache, kv_shard_axis)
            if _use_fused_decode(window, kv_x, idx, sq):
                # zero-copy step: the fused read walks the pool through
                # the block table, so the [B, size] gather never happens
                return _fused_decode_epilogue(
                    p, cfg, q, new_cache, idx + vlen, positions, kv_bits,
                    new_cache, qm, kv_shard_axis, block_tables=bt)
            read_cache, kv_dtype = new_cache, k.dtype
            kv_fn = lambda: _paged_cache_read(read_cache, bt, kv_dtype,
                                              kv_bits, hd)

            def mask_fn(qpos):
                kp = kv_pos[:, None, :]
                m = kp <= qpos[:, :, None]
                m &= kp >= 0
                return m

            kv_view_len = size
            return _attention_epilogue(p, cfg, q, kv_fn, mask_fn,
                                       positions, q_chunk, kv_view_len,
                                       kv_bits, new_cache, qm)
        size = cache["k"].shape[1]
        if idx.ndim == 0:
            # lockstep scalar path: every row writes the same slot
            slot = idx % size if window else idx
            with jax.named_scope("kv_write"):
                new_cache = _cache_write(cache, k, v, slot, kv_bits)
            kv_pos = _ring_positions(idx, size, window)        # [size]
        else:
            # per-slot positions: row b writes its window at absolute
            # positions idx[b]..idx[b]+sq-1; tokens past cache_valid[b]
            # are dropped so ragged rows never corrupt the ring
            if window and sq > 1:
                raise NotImplementedError(
                    "chunked ragged prefill over a sliding-window ring "
                    "would overwrite slots still visible to earlier "
                    "queries of the same window; feed ring-cache archs "
                    "token-by-token (ServingEngine clamps prefill_chunk "
                    "to 1 for them)")
            vlen = (jnp.full((b,), sq, jnp.int32) if cache_valid is None
                    else jnp.asarray(cache_valid, jnp.int32))
            offs = jnp.arange(sq, dtype=jnp.int32)
            wpos = idx[:, None] + offs[None, :]                # [B, sq]
            slots = wpos % size if window else wpos
            with jax.named_scope("kv_write"):
                new_cache = _cache_write_ragged(
                    cache, k, v, slots, offs[None, :] < vlen[:, None],
                    kv_bits)
            kv_pos = _ring_positions_batch(idx + vlen - 1, size,
                                           window)            # [B, size]
        new_cache = _constrain_kv_heads(new_cache, kv_shard_axis)
        if _use_fused_decode(window, kv_x, idx, sq):
            valid_len = (jnp.full((b,), idx + sq, jnp.int32)
                         if idx.ndim == 0 else idx + vlen)
            return _fused_decode_epilogue(p, cfg, q, new_cache, valid_len,
                                          positions, kv_bits, new_cache,
                                          qm, kv_shard_axis)
        # deferred read: _chunked_attention calls this inside the chunk
        # body, so a packed cache is unpacked+dequantized fused with the
        # score/value einsums (the bf16 cache copy never exists whole)
        read_cache, kv_dtype = new_cache, k.dtype
        kv_fn = lambda: _cache_read(read_cache, kv_dtype, kv_bits, hd)

        def mask_fn(qpos):
            kp = kv_pos[:, None, :] if kv_pos.ndim == 2 \
                else kv_pos[None, None, :]
            m = kp <= qpos[:, :, None]
            m &= kp >= 0
            if window:
                m &= (qpos[:, :, None] - kp) < window
            return m
    else:
        # ---- training / prefill ----
        kv_fn = lambda: (k, v)  # attends over the raw (unquantized) k/v
        stored = None
        if (quant_mode == "packed" and kv_bits in (8, 4, 2)
                and kv_x is None and causal and not window):
            # the deployed model attends to K/V as its cache stores them
            # (DESIGN.md §13): the packed forward quantizes this window's
            # K/V at the cache precision and reads them back the way
            # decode does — the fused read, or the legacy dequantizing
            # read under the REPRO_FUSED_DECODE=0 kill-switch
            stored = {}
            with jax.named_scope("kv_write"):
                stored["k"], stored["k_scale"] = _kv_quantize(k, kv_bits)
                stored["v"], stored["v_scale"] = _kv_quantize(v, kv_bits)
                if cache is not None:
                    new_cache = _constrain_kv_heads(
                        _cache_write(cache, k, v, 0, kv_bits),
                        kv_shard_axis)
            from repro.kernels import ulppack_attention
            if ulppack_attention.enabled():
                causal_idx = jnp.broadcast_to(jnp.arange(sq)[None, :],
                                              (b, sq))
                return _fused_decode_epilogue(
                    p, cfg, q, stored, jnp.full((b,), sq, jnp.int32),
                    causal_idx, kv_bits, new_cache, qm, None)
            kv_fn = lambda: _cache_read(stored, k.dtype, kv_bits, hd)
        if cache is not None and stored is None:  # prefill fills the cache
            size = cache["k"].shape[1]
            with jax.named_scope("kv_write"):
                if window and sq > size:
                    # ring layout: slot = pos % size for the last `size`
                    # tokens
                    roll = (sq % size)
                    new_cache = _cache_write(cache, k[:, -size:],
                                             v[:, -size:], 0, kv_bits)
                    new_cache = {kk: jnp.roll(vv, roll, axis=1)
                                 for kk, vv in new_cache.items()}
                else:
                    new_cache = _cache_write(cache, k, v, 0, kv_bits)
                new_cache = _constrain_kv_heads(new_cache, kv_shard_axis)
        if kv_x is not None:
            kv_pos = (kv_positions if kv_positions is not None
                      else jnp.arange(k.shape[1]))[None, :]

            def mask_fn(qpos):
                return jnp.broadcast_to(
                    kv_pos[:, None, :] >= 0,
                    (qpos.shape[0], qpos.shape[1], k.shape[1]))
        else:
            kv_pos = positions

            def mask_fn(qpos):
                kp = kv_pos[:, None, :] if kv_pos.ndim == 2 \
                    else kv_pos[None, None, :]
                m = jnp.ones((qpos.shape[0], qpos.shape[1], k.shape[1]),
                             bool)
                if causal:
                    m &= kp <= qpos[:, :, None]
                if window:
                    m &= (qpos[:, :, None] - kp) < window
                return m

    skv = (cache["k"].shape[1] if cache is not None
           and cache_index is not None else k.shape[1])
    return _attention_epilogue(p, cfg, q, kv_fn, mask_fn, positions,
                               q_chunk, skv, kv_bits, new_cache, qm)


def _cache_write(cache, k, v, slot, kv_bits=0):
    """Write a [B, s, KVH, hd] float slice at `slot` (quantizing — and for
    sub-byte ``kv_bits`` word-packing along head_dim — when the cache is
    quantized)."""
    dus = jax.lax.dynamic_update_slice_in_dim
    if "k_scale" in cache:
        qk, sk = _kv_quantize(k, kv_bits)
        qv, sv = _kv_quantize(v, kv_bits)
        return {"k": dus(cache["k"], qk, slot, 1),
                "v": dus(cache["v"], qv, slot, 1),
                "k_scale": dus(cache["k_scale"], sk, slot, 1),
                "v_scale": dus(cache["v_scale"], sv, slot, 1)}
    return {"k": dus(cache["k"], k.astype(cache["k"].dtype), slot, 1),
            "v": dus(cache["v"], v.astype(cache["v"].dtype), slot, 1)}


def _cache_write_ragged(cache, k, v, slots, valid, kv_bits=0):
    """Per-row ragged write: token j of row b lands at ring slot
    ``slots[b, j]``; tokens with ``valid[b, j]`` False are redirected out
    of bounds and dropped (scatter ``mode='drop'``), so pad tokens never
    overwrite live entries.  O(window tokens) per call — the decode hot
    path writes one slot per row, like the lockstep ``_cache_write``.

    Callers guarantee a row never writes the same slot twice in one call
    (the windowed sq > 1 case is rejected upstream), so scatter duplicate
    semantics are never exercised.
    """
    size = cache["k"].shape[1]
    bi = jnp.arange(k.shape[0], dtype=jnp.int32)[:, None]
    tgt = jnp.where(valid, slots, size)

    def put(buf, val):
        return buf.at[bi, tgt].set(val.astype(buf.dtype), mode="drop")

    if "k_scale" in cache:
        qk, sk = _kv_quantize(k, kv_bits)
        qv, sv = _kv_quantize(v, kv_bits)
        return {"k": put(cache["k"], qk), "v": put(cache["v"], qv),
                "k_scale": put(cache["k_scale"], sk),
                "v_scale": put(cache["v_scale"], sv)}
    return {"k": put(cache["k"], k), "v": put(cache["v"], v)}


def _cache_write_paged(cache, k, v, pages, rows, valid, kv_bits=0):
    """Block-table scatter: token j of row b lands at physical page
    ``pages[b, j]``, row ``rows[b, j]`` of the pool.  Invalid tokens are
    redirected past the pool (scatter ``mode='drop'``), exactly like the
    ragged ring write.  Quantization/word-packing happen per incoming
    token row, so the stored words and scale planes are value-identical
    to the unpaged layout at the same absolute positions."""
    num_pages = cache["k"].shape[0]
    tgt = jnp.where(valid, pages, num_pages)

    def put(buf, val):
        return buf.at[tgt, rows].set(val.astype(buf.dtype), mode="drop")

    if "k_scale" in cache:
        qk, sk = _kv_quantize(k, kv_bits)
        qv, sv = _kv_quantize(v, kv_bits)
        return {"k": put(cache["k"], qk), "v": put(cache["v"], qv),
                "k_scale": put(cache["k_scale"], sk),
                "v_scale": put(cache["v_scale"], sv)}
    return {"k": put(cache["k"], k), "v": put(cache["v"], v)}


def _paged_cache_read(cache, block_tables, dtype, kv_bits=0, hd=None):
    """Gather each row's pages into the logical [B, n_pages*ps, KVH, ...]
    view and dequantize.  Called inside the q-chunk body (kv_fn), so the
    gather + fused unpack/dequant stay per chunk — the full-precision
    cache never exists whole, same as the unpaged read path."""
    def gather(buf):
        g = buf[block_tables]                # [B, n_pages, ps, KVH, ...]
        return g.reshape(g.shape[0], -1, *g.shape[3:])

    if "k_scale" in cache:
        return (_kv_dequantize(gather(cache["k"]), gather(cache["k_scale"]),
                               dtype, kv_bits, hd),
                _kv_dequantize(gather(cache["v"]), gather(cache["v_scale"]),
                               dtype, kv_bits, hd))
    return gather(cache["k"]), gather(cache["v"])


def _ring_positions_batch(last, size, window):
    """Batched `_ring_positions`: absolute positions stored per ring slot
    for each row given its last written position ``last [B]`` (-1 = row
    empty).  Plain broadcast arithmetic (no vmap)."""
    slots = jnp.arange(size, dtype=jnp.int32)[None, :]
    last = last[:, None]
    if not window:
        return jnp.where(slots <= last, slots, -1)
    cur_slot = last % size
    pos = last - ((cur_slot - slots) % size)
    return jnp.where(pos >= 0, pos, -1)


def _cache_read(cache, dtype, kv_bits=0, hd=None):
    if "k_scale" in cache:
        return (_kv_dequantize(cache["k"], cache["k_scale"], dtype,
                               kv_bits, hd),
                _kv_dequantize(cache["v"], cache["v_scale"], dtype,
                               kv_bits, hd))
    return cache["k"], cache["v"]


def _ring_positions(cache_index, size, window):
    """Absolute positions stored in each ring slot (-1 = empty)."""
    slots = jnp.arange(size)
    if not window:
        pos = slots
        return jnp.where(slots <= cache_index, pos, -1)
    # slot s holds the latest position p <= cache_index with p % size == s
    cur_slot = cache_index % size
    pos = cache_index - ((cur_slot - slots) % size)
    return jnp.where(pos >= 0, pos, -1)
