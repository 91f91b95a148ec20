"""Fused sub-byte decode attention: flash-decoding over the packed KV cache.

The serving decode hot path used to read the KV cache through
``_cache_read`` / ``_paged_cache_read``: dequantize (or gather, for the
paged pool) the ENTIRE allocated view, then run a two-pass softmax over a
full ``[C, Sk]`` score block.  Sub-byte storage pays for itself only while
the packed words stay packed until the compute instruction (the paper's
``vmacsr`` discipline; FullPack/Quark make the same point) — so this module
restructures decode attention as flash-decoding (DESIGN.md §20):

  * the KV length is split into groups (``plan.block_k`` token rows;
    ``plan.chunks`` block-table pages per group when paged) and each group
    is unpacked, dequantized and contracted in registers/VMEM;
  * a running (max, sum, accumulator) carry combines groups — the online
    softmax — so no full score block ever materializes;
  * paged caches are walked group-by-group THROUGH the block table (the
    whole-view ``pool[block_tables]`` gather copy disappears);
  * groups entirely past every row's live length are skipped with a
    ``lax.cond`` — the old path paid O(allocated), this one pays O(live);
  * sub-byte scores fold the midpoint zero-point into the contraction:
    ``s = scale_k * (q . u - zp * sum(q))`` and the value side
    ``out += (p * scale_v) . u - zp * sum(p * scale_v)`` keep the lattice
    integer until the per-group epilogue.

Two registered backends for the ``attention_decode`` op:

  'xla'    — the algorithm above in plain jnp (python-unrolled group loop).
             This is the deployed CPU path, the chip's path for windows of
             more than one query row, and GSPMD partitions it under a mesh.
  'pallas' — the real kernel: grid (batch, kv-split), online-softmax carry
             in VMEM scratch, shift-mask word unpack in-kernel, and — paged
             — a scalar-prefetched block table whose entries ARE the
             kv-split block indices (``PrefetchScalarGridSpec``), i.e. the
             block-table walk happens in the kernel's index_map.  Runs
             interpreted off-TPU (plan.default_interpret()).

``fused_decode_attention`` is the models/attention.py entry point; the
``REPRO_FUSED_DECODE=0`` environment kill-switch (read at trace time;
launch/steps.py keys its jit memo on it) restores the legacy read path.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.reduce import ordered_sum
from repro.kernels import plan as plan_lib

NEG_INF = -1e30

#: Environment kill-switch: "0" disables the fused decode path everywhere
#: (models/attention.py falls back to the legacy whole-view read).  Read at
#: trace time — launch/steps.py includes :func:`enabled` in its jit memo
#: keys so flipping the flag never hits a stale trace.
ENV_FLAG = "REPRO_FUSED_DECODE"


def enabled() -> bool:
    return os.environ.get(ENV_FLAG, "1") != "0"


@contextlib.contextmanager
def disabled():
    """Context manager: run with the fused decode path off (tests use this
    to produce legacy-path references from the same process)."""
    old = os.environ.get(ENV_FLAG)
    os.environ[ENV_FLAG] = "0"
    try:
        yield
    finally:
        if old is None:
            os.environ.pop(ENV_FLAG, None)
        else:
            os.environ[ENV_FLAG] = old


# ---------------------------------------------------------------------------
# Shared pieces
# ---------------------------------------------------------------------------

def _unpack_group(words, bits, hd):
    """int32 words [..., hdw] -> f32 lattice values [..., hd] (the shift/
    mask expansion of packing.unpack_words, ascending field order)."""
    per = 32 // bits
    mask = (1 << bits) - 1
    shifts = jnp.arange(per, dtype=jnp.int32) * bits
    vals = (words[..., None] >> shifts) & mask          # [..., hdw, per]
    vals = vals.reshape(*words.shape[:-1], words.shape[-1] * per)
    return vals[..., :hd].astype(jnp.float32)


def _prep_q(q, kvh):
    """[B, C, H, hd] -> pre-scaled f32 [B, C, KVH, G, hd] + row sums."""
    b, c, h, hd = q.shape
    qg = (q.astype(jnp.float32) * hd ** -0.5).reshape(b, c, kvh,
                                                      h // kvh, hd)
    return qg, ordered_sum(qg)


def _combine(carry, s, ok, u_v, ssv, zp):
    """One online-softmax step: fold a group's masked scores ``s``
    [B, C, KVH, G, L] and values ``u_v`` [B, L, KVH, hd] into the running
    (max, sum, acc) carry.  ``ssv`` is the group's value-scale plane
    broadcast like ``s`` (None for float caches), ``zp`` the lattice
    midpoint (0 for symmetric/float storage).  Sums run in a fixed order
    (core/reduce.py), so a query row scores the same in a one-row decode
    step, a 32-row prefill window and an uncached forward."""
    m, l, acc = carry
    s = jnp.where(ok, s, NEG_INF)
    mn = jnp.maximum(m, jnp.max(s, axis=-1))
    corr = jnp.exp(m - mn)
    p = jnp.where(ok, jnp.exp(s - mn[..., None]), 0.0)
    l2 = l * corr + ordered_sum(p)
    pv = p if ssv is None else p * ssv
    av = jnp.einsum("bckgs,bskd->bckgd", pv, u_v,
                    preferred_element_type=jnp.float32)
    if zp:
        av = av - (zp * ordered_sum(pv))[..., None]
    return mn, l2, acc * corr[..., None] + av


def _group_scores(qg, qsum, gk, gsk, kv_bits, hd, zp):
    """Scores of one KV group: ``gk`` is the group's stored K ([B, L, KVH,
    hd] float, [B, L, KVH, hd] int8, or [B, L, KVH, hdw] packed words),
    ``gsk`` its scale plane [B, L, KVH] (None for float caches).
    Returns scores [B, C, KVH, G, L]."""
    u = (_unpack_group(gk, kv_bits, hd) if kv_bits in (4, 2)
         else gk.astype(jnp.float32))
    s = jnp.einsum("bckgd,bskd->bckgs", qg, u,
                   preferred_element_type=jnp.float32)
    if gsk is not None:
        ss = gsk.astype(jnp.float32).transpose(0, 2, 1)[:, None, :, None, :]
        s = ss * (s - zp * qsum[..., None] if zp else s)
    return s


def _finish(carry, b, c, h, hd, out_dtype):
    m, l, acc = carry
    out = acc / jnp.where(l == 0, 1.0, l)[..., None]
    return out.reshape(b, c, h, hd).astype(out_dtype)


def _scale_broadcast(gsv):
    if gsv is None:
        return None
    return gsv.astype(jnp.float32).transpose(0, 2, 1)[:, None, :, None, :]


# ---------------------------------------------------------------------------
# 'xla' backend — fused flash-decoding in plain jnp (CPU / sharded serving)
# ---------------------------------------------------------------------------

@plan_lib.register_backend("attention_decode", "xla")
def _attention_decode_xla(plan, q, cache, valid_len, qpos,
                          block_tables=None, *, kv_bits, hd):
    """Python-unrolled group loop; each group guarded by a ``lax.cond`` on
    ``group_start < max(valid_len)`` so fully-dead groups cost one scalar
    compare instead of an unpack + two contractions."""
    b, c, h, _ = q.shape
    kvh = cache["k"].shape[2]
    zp = (1 << (kv_bits - 1)) if kv_bits in (4, 2) else 0
    quantized = "k_scale" in cache
    qg, qsum = _prep_q(q, kvh)
    groups = h // kvh
    carry = (jnp.full((b, c, kvh, groups), NEG_INF, jnp.float32),
             jnp.zeros((b, c, kvh, groups), jnp.float32),
             jnp.zeros((b, c, kvh, groups, hd), jnp.float32))
    live_max = jnp.max(valid_len)

    if block_tables is not None:
        page_rows = cache["k"].shape[1]
        n_pages = block_tables.shape[1]
        pp = max(1, plan.chunks or 1)
        starts = range(0, n_pages, pp)
    else:
        skv = cache["k"].shape[1]
        bk = max(1, plan.block_k or skv)
        starts = range(0, skv, bk)

    for g0 in starts:
        if block_tables is not None:
            t0 = g0 * page_rows

            def read(g0=g0):
                pages = block_tables[:, g0:g0 + pp]
                span = pages.shape[1] * page_rows

                def gather(buf):
                    gg = buf[pages]
                    return gg.reshape(b, span, *gg.shape[3:])
                gk, gv = gather(cache["k"]), gather(cache["v"])
                gsk = gather(cache["k_scale"]) if quantized else None
                gsv = gather(cache["v_scale"]) if quantized else None
                return gk, gv, gsk, gsv, span
        else:
            t0 = g0

            def read(g0=g0):
                sl = slice(g0, g0 + bk)
                gk, gv = cache["k"][:, sl], cache["v"][:, sl]
                gsk = cache["k_scale"][:, sl] if quantized else None
                gsv = cache["v_scale"][:, sl] if quantized else None
                return gk, gv, gsk, gsv, gk.shape[1]

        def body(carry, read=read, t0=t0):
            gk, gv, gsk, gsv, span = read()
            s = _group_scores(qg, qsum, gk, gsk, kv_bits, hd, zp)
            pos = t0 + jnp.arange(span, dtype=jnp.int32)
            ok = ((pos[None, None, :] < valid_len[:, None, None])
                  & (pos[None, None, :] <= qpos[:, :, None]))
            ok = ok[:, :, None, None, :]
            u_v = (_unpack_group(gv, kv_bits, hd) if kv_bits in (4, 2)
                   else gv.astype(jnp.float32))
            return _combine(carry, s, ok, u_v, _scale_broadcast(gsv), zp)

        carry = jax.lax.cond(t0 < live_max, body, lambda cr: cr, carry)

    return _finish(carry, b, c, h, hd, q.dtype)


# ---------------------------------------------------------------------------
# 'pallas' backend — the real kernel (interpreted off-TPU)
#
# Mosaic lowers plain 2-D matmuls, not the [span, KVH, hd] head-batched
# contractions or the in-register word reshapes of the 'xla' path, so the
# kernel works on a lane-flat cache row: one token row is its KVH kv-head
# words side by side, [KVH * hdw] lanes (a free reshape of the stored
# cache).  Field j of every word is one shift-mask plane u_j [span, KVH*hdw]
# holding dims w*per + j of each head.  The query side is laid out to match
# in the wrapper: per field j, a block-diagonal [H, KVH*hdw] matrix whose
# row (kvh, g) holds that query head's dims w*per + j in kv head kvh's
# lane block and zeros elsewhere.  Then
#
#     scores [H, span] = sum_j  qbd_j @ u_j^T
#     acc_j  [H, KVH*hdw] += p @ v_j
#
# are plain 2-D MXU matmuls, and the wrapper reads each head's output off
# the diagonal block of acc.  Per-(pos, kv-head) scale planes expand to
# [H, span] rows through a one-hot [H, KVH] matmul.  Unpacked caches (bf16,
# int8) are the per = 1 case.
# ---------------------------------------------------------------------------

_HI = jax.lax.Precision.HIGHEST


def _decode_kernel(*refs, kv_bits, per, zp, span, paged, quantized):
    """Grid (B, n_groups): one batch row x one KV group per program; the
    online-softmax carry lives in VMEM scratch across the group sweep.
    Group j covers logical token rows j*span .. j*span+span (for the paged
    variant the index_map already picked the group's pool page)."""
    if paged:
        _, vl_ref, qp_ref, *refs = refs
    else:
        vl_ref, qp_ref, *refs = refs
    if quantized:
        (qbd_ref, qs_ref, e_ref, k_ref, v_ref, sk_ref, sv_ref, o_ref,
         m_ref, l_ref, acc_ref) = refs
    else:
        (qbd_ref, qs_ref, e_ref, k_ref, v_ref, o_ref,
         m_ref, l_ref, acc_ref) = refs
    i, j = pl.program_id(0), pl.program_id(1)
    valid_len, qpos = vl_ref[i], qp_ref[i]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def planes(ref):
        x = ref[0]
        if per == 1:
            return [x.astype(jnp.float32)]
        mask = (1 << kv_bits) - 1
        return [((x >> (kv_bits * f)) & mask).astype(jnp.float32)
                for f in range(per)]

    def expand(scale_ref):
        # [span, KVH] scale plane -> [H, span] rows via the one-hot E
        return jax.lax.dot_general(
            e_ref[...], scale_ref[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())), precision=_HI,
            preferred_element_type=jnp.float32)

    @pl.when(j * span < valid_len)          # groups past every live row skip
    def _group():
        u_k = planes(k_ref)
        s = None
        for f in range(per):
            t = jax.lax.dot_general(qbd_ref[0, f], u_k[f],
                                    (((1,), (1,)), ((), ())), precision=_HI,
                                    preferred_element_type=jnp.float32)
            s = t if s is None else s + t                   # [H, span]
        if quantized:
            s = expand(sk_ref) * (s - zp * qs_ref[0] if zp else s)
        pos = j * span + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
        ok = (pos < valid_len) & (pos <= qpos)
        s = jnp.where(ok, s, NEG_INF)
        m = m_ref[...]
        mn = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m - mn)
        p = jnp.where(ok, jnp.exp(s - mn), 0.0)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = mn
        if quantized:
            p = p * expand(sv_ref)
        psum = jnp.sum(p, axis=1, keepdims=True)
        for f, u in enumerate(planes(v_ref)):
            av = jax.lax.dot_general(p, u, (((1,), (0,)), ((), ())),
                                     precision=_HI,
                                     preferred_element_type=jnp.float32)
            if zp:
                av = av - zp * psum
            acc_ref[f] = acc_ref[f] * corr + av

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        ll = l_ref[...]
        o_ref[0] = acc_ref[...] / jnp.where(ll == 0, 1.0, ll)[None]


def _pad_tokens(x, multiple):
    rem = (-x.shape[1]) % multiple
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[1] = (0, rem)
    return jnp.pad(x, pad)


def _block_diag_query(q, kvh, per, hdw):
    """q [B, 1, H, hd] -> pre-scaled block-diagonal field planes
    [B, per, H, KVH*hdw] (see the section comment) and the per-head
    query sums [B, H, 1] the zero-point term needs."""
    b, _, h, hd = q.shape
    qg, qsum = _prep_q(q, kvh)
    qg = qg[:, 0]                                      # [B, KVH, G, hd]
    qf = jnp.pad(qg, ((0, 0),) * 3 + ((0, hdw * per - hd),))
    qf = qf.reshape(b, kvh, h // kvh, hdw, per).transpose(0, 4, 1, 2, 3)
    # elementwise with the identity (a dot would round q to bf16 on TPU)
    eye = jnp.eye(kvh, dtype=jnp.float32)[:, None, :, None]
    qbd = qf[:, :, :, :, None, :] * eye        # [B, per, KVH, G, KVH, hdw]
    return (qbd.reshape(b, per, h, kvh * hdw),
            qsum[:, 0].reshape(b, h, 1))


@plan_lib.register_backend("attention_decode", "pallas")
def _attention_decode_pallas(plan, q, cache, valid_len, qpos,
                             block_tables=None, *, kv_bits, hd):
    """Pallas flash-decoding kernel; sq == 1 decode only (the dispatcher
    routes wider windows to the 'xla' backend).

    Contiguous: grid (B, ceil(Sk / block_k)), token-sliced BlockSpecs.
    Paged: grid (B, n_pages) — the scalar-prefetched block table IS the
    pool index_map (``bt[i, j]``), one page per grid step, so the kernel
    walks each row's page list without materializing the gathered view.
    ``valid_len``/``qpos`` ride in SMEM as scalar-prefetch operands."""
    b, c, h, _ = q.shape
    if c != 1:
        raise ValueError("pallas attention_decode handles sq == 1 only")
    kvh = cache["k"].shape[2]
    groups = h // kvh
    packed = kv_bits in (4, 2)
    per = 32 // kv_bits if packed else 1
    zp = (1 << (kv_bits - 1)) if packed else 0
    quantized = "k_scale" in cache
    hdw = cache["k"].shape[-1]
    width = kvh * hdw
    qbd, qsum = _block_diag_query(q, kvh, per, hdw)
    onehot = (jnp.arange(h)[:, None] // groups
              == jnp.arange(kvh)[None, :]).astype(jnp.float32)
    vl = valid_len.astype(jnp.int32).reshape(b)
    qp = qpos[:, 0].astype(jnp.int32).reshape(b)
    paged = block_tables is not None

    def flat(x):            # [N, rows, KVH, hdw] -> [N, rows, KVH*hdw]
        return x.reshape(*x.shape[:2], -1)

    if paged:
        span = cache["k"].shape[1]
        n_groups = block_tables.shape[1]
        bt = jnp.clip(block_tables.astype(jnp.int32), 0,
                      cache["k"].shape[0] - 1)
        prefetch = (bt, vl, qp)
        kv_map = lambda i, j, bt_, vl_, qp_: (bt_[i, j], 0, 0)
        row_map = lambda i, j, *_: (i, 0, 0)
        row4_map = lambda i, j, *_: (i, 0, 0, 0)
        const_map = lambda i, j, *_: (0, 0)
        ks, vs = flat(cache["k"]), flat(cache["v"])
        scales = [cache["k_scale"], cache["v_scale"]] if quantized else []
    else:
        skv = cache["k"].shape[1]
        span = min(max(1, plan.block_k or skv), skv)
        if span < skv:
            span = -(-span // 8) * 8
        prefetch = (vl, qp)
        kv_map = lambda i, j, vl_, qp_: (i, j, 0)
        row_map = lambda i, j, *_: (i, 0, 0)
        row4_map = lambda i, j, *_: (i, 0, 0, 0)
        const_map = lambda i, j, *_: (0, 0)
        ks = _pad_tokens(flat(cache["k"]), span)
        vs = _pad_tokens(flat(cache["v"]), span)
        scales = ([_pad_tokens(cache["k_scale"], span),
                   _pad_tokens(cache["v_scale"], span)] if quantized
                  else [])
        n_groups = ks.shape[1] // span

    in_specs = [pl.BlockSpec((1, per, h, width), row4_map),
                pl.BlockSpec((1, h, 1), row_map),
                pl.BlockSpec((h, kvh), const_map),
                pl.BlockSpec((1, span, width), kv_map),
                pl.BlockSpec((1, span, width), kv_map)]
    in_specs += [pl.BlockSpec((1, span, kvh), kv_map) for _ in scales]
    kern = functools.partial(_decode_kernel, kv_bits=kv_bits, per=per,
                             zp=zp, span=span, paged=paged,
                             quantized=quantized)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, n_groups),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, per, h, width), row4_map),
        scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((per, h, width), jnp.float32)])
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, per, h, width), jnp.float32),
        interpret=plan.interpret, name="ulppack_attention_decode",
    )(*prefetch, qbd, qsum, onehot, ks, vs, *scales)
    # each head's output is the diagonal (own kv head) block of acc
    out = out.reshape(b, per, kvh, groups, kvh, hdw)
    eye = jnp.eye(kvh, dtype=jnp.float32)[:, None, :, None]
    out = jnp.sum(out * eye, axis=4)           # [B, per, KVH, G, hdw]
    out = out.transpose(0, 2, 3, 4, 1).reshape(b, h, hdw * per)[..., :hd]
    return out.reshape(b, 1, h, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Entry point (models/attention.py)
# ---------------------------------------------------------------------------

def fused_decode_attention(q, cache, valid_len, qpos, *, kv_bits, hd,
                           plan=None, block_tables=None, backend="auto",
                           shard_axis=None):
    """Flash-decoding attention over the stored (possibly packed) cache.

    q [B, C, H, hd]; ``cache`` the stored layout (init_kv_cache /
    init_paged_kv_cache); ``valid_len`` [B] live token rows per sequence
    (logical-view prefix); ``qpos`` [B, C] absolute query positions.
    ``plan`` defaults to :func:`plan_attention_decode` for the shape;
    the 'pallas' backend serves C == 1 only (wider verify windows route
    to 'xla').  ``shard_axis`` names the mesh axis the serving cache's
    kv heads shard over (DESIGN.md §15): the 'xla' backend is left to
    GSPMD, the Pallas kernel runs once per head shard under shard_map.
    Returns [B, C, H, hd] in q.dtype.
    """
    b, c, h, _ = q.shape
    kvh = cache["k"].shape[2]
    backend = plan.backend if plan is not None \
        else plan_lib.resolve_backend(backend)
    if backend == "pallas" and c != 1:
        backend = "xla"

    def plan_for(shards):
        if plan is not None:
            return dataclasses.replace(plan, backend=backend)
        page_size = cache["k"].shape[1] if block_tables is not None else None
        skv = (block_tables.shape[1] * cache["k"].shape[1]
               if block_tables is not None else cache["k"].shape[1])
        return plan_lib.plan_attention_decode(
            b, skv, h // shards, kvh // shards, hd, kv_bits,
            page_size=page_size, backend=backend)

    def layout(mesh):
        # the cache's kv heads lie on ``shard_axis`` where it divides them
        # (the serving ShardPlan's rule); queries and outputs follow them
        from jax.sharding import PartitionSpec as P

        from repro.parallel.sharding import _axis_size, spec_on_mesh
        axis = spec_on_mesh(mesh, (kvh,), shard_axis)[0] \
            if shard_axis is not None else None
        heads = P(None, None, axis, None)
        cache_spec = {k: P(None, None, axis, *([None] * (v.ndim - 3)))
                      for k, v in cache.items()}
        return ((heads, cache_spec, P(), P(),
                 None if block_tables is None else P()), heads,
                plan_for(_axis_size(mesh, axis)))

    return plan_lib.dispatch_on_mesh(
        plan_for(1), (q, cache, jnp.asarray(valid_len, jnp.int32),
                      jnp.asarray(qpos, jnp.int32), block_tables),
        layout, kv_bits=kv_bits, hd=hd)
