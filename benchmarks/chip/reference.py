"""Plain reference of the served model, written from the model's equations.

It imports nothing of the program and takes nothing the program made: it
reads the float weights that weights.py draws from the seed and quantizes
them itself.  One full causal forward over each prompt followed by its
served tokens (teacher forcing), no cache, no pages, no chunks, no
kernels.

The block (the repository's dense decoder; configs/*.json say where it
departs from the published model):

    h   = RMSNorm(x)
    q,k,v = W2A2(h)            k, v rotated (RoPE, full head) with q
    k,v stored at kv_bits:     per (position, kv head) absmax step
                               amax / (qmax - zp), midpoint zp, the step
                               kept in bfloat16
    x  += W2A2(softmax(q k^T / sqrt(hd)) v)    GQA: head h reads kv head
                                               h // (H / KVH)
    h   = RMSNorm(x)
    x  += W2A2(silu(W2A2_gate(h)) * W2A2_up(h))
    logits = RMSNorm(x) @ lm_head   (tied: @ embedding^T)

W2A2(h) = a_step * w_step * (qa - 2) . (qw - 2), with qa = clip(round(h /
a_step) + 2, 0, 3) and qw = clip(round(w / w_step) + 2, 0, 3): the lattice
dot is exact in integers.  Every float operation runs in float32 at
``highest`` matmul precision; values the configuration keeps in its
compute dtype (``cdt``: the residual stream, each projection's output, the
rotated q/k, the attention output, the normed inputs) are rounded to it
where the model stores them.  The control runs the same code with ``cdt``
one precision lower (float8_e4m3fn for bfloat16).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import weights as weights_lib

#: query rows per attention block: bounds the [B, H, rows, S] score block
Q_BLOCK = 512
#: vocabulary columns per head block: bounds the [B, S, cols] logits block
V_BLOCK = 8192


def _act_lattice(h, a_step, bits):
    zp = 1 << (bits - 1)
    q = jnp.clip(jnp.round(h.astype(jnp.float32) / a_step) + zp, 0,
                 (1 << bits) - 1)
    return (q - zp).astype(jnp.int8)


def _w_lattice(kernel, w_step, bits):
    zp = 1 << (bits - 1)
    q = jnp.clip(jnp.round(kernel.astype(jnp.float32) / w_step) + zp, 0,
                 (1 << bits) - 1)
    return (q - zp).astype(jnp.int8)


def linear(h, p, bits, cdt):
    qa = _act_lattice(h, p["a_step"], bits[1])
    qw = _w_lattice(p["kernel"], p["w_step"], bits[0])
    acc = jax.lax.dot_general(qa, qw, (((qa.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    scale = p["a_step"].astype(jnp.float32) * p["w_step"].astype(jnp.float32)
    return (scale * acc.astype(jnp.float32)).astype(cdt)


def rmsnorm(x, scale, eps, cdt):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32)).astype(cdt)


def rope(x, pos, theta, cdt):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(cdt)


def kv_store(x, bits):
    """The cache's stored value of ``x`` [B, S, KVH, hd], read back."""
    zp, qmax = 1 << (bits - 1), (1 << bits) - 1
    x32 = x.astype(jnp.float32)
    step = jnp.maximum(jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
                       / (qmax - zp), 1e-8)
    u = jnp.clip(jnp.round(x32 / step) + zp, 0, qmax)
    return (u - zp) * step.astype(jnp.bfloat16).astype(jnp.float32)


def attention(q, k, v, cdt):
    """Causal softmax attention, q [B, S, H, hd], k/v [B, S, KVH, hd]."""
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    qg = (q.astype(jnp.float32) * hd ** -0.5).reshape(b, s, kvh, h // kvh,
                                                      hd)
    outs = []
    for r0 in range(0, s, Q_BLOCK):
        qb = qg[:, r0:r0 + Q_BLOCK]
        rows = r0 + jnp.arange(qb.shape[1])
        sc = jnp.einsum("bqkgd,bskd->bkgqs", qb, k)
        ok = jnp.arange(s)[None, :] <= rows[:, None]
        sc = jnp.where(ok, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("bkgqs,bskd->bqkgd", pr, v))
    return jnp.concatenate(outs, axis=1).reshape(b, s, h, hd).astype(cdt)


@functools.partial(jax.jit, static_argnames=("cfg_key", "cdt"))
def _layer(x, p, pos, *, cfg_key, cdt):
    cfg = dict(cfg_key)
    bits = (cfg["w_bits"], cfg["a_bits"])
    b, s, _ = x.shape
    hd, h, kvh = cfg["head_dim"], cfg["num_heads"], cfg["num_kv_heads"]
    with jax.default_matmul_precision("highest"):
        a = rmsnorm(x, p["norm1"]["scale"], cfg["norm_eps"], cdt)
        q = linear(a, p["attn"]["q"], bits, cdt).reshape(b, s, h, hd)
        k = linear(a, p["attn"]["k"], bits, cdt).reshape(b, s, kvh, hd)
        v = linear(a, p["attn"]["v"], bits, cdt).reshape(b, s, kvh, hd)
        q = rope(q, pos, cfg["rope_theta"], cdt)
        k = rope(k, pos, cfg["rope_theta"], cdt)
        att = attention(q, kv_store(k, cfg["kv_bits"]),
                        kv_store(v, cfg["kv_bits"]), cdt)
        o = linear(att.reshape(b, s, h * hd), p["attn"]["o"], bits, cdt)
        x = (x.astype(jnp.float32) + o.astype(jnp.float32)).astype(cdt)
        a = rmsnorm(x, p["norm2"]["scale"], cfg["norm_eps"], cdt)
        up = linear(a, p["mlp"]["up"], bits, cdt)
        gate = linear(a, p["mlp"]["gate"], bits, cdt)
        mid = jax.nn.silu(gate) * up
        down = linear(mid, p["mlp"]["down"], bits, cdt)
        return (x.astype(jnp.float32) + down.astype(jnp.float32)).astype(cdt)


@functools.partial(jax.jit, static_argnames=("eps", "cdt"))
def _final(x, scale, *, eps, cdt):
    return rmsnorm(x, scale, eps, cdt).astype(jnp.float32)


def _cfg_key(cfg: dict) -> tuple:
    return tuple(sorted({
        "w_bits": cfg["w_bits"], "a_bits": cfg["a_bits"],
        "kv_bits": cfg["kv_bits"], "head_dim": weights_lib.head_dim(cfg),
        "num_heads": cfg["num_heads"], "num_kv_heads": cfg["num_kv_heads"],
        "norm_eps": cfg["norm_eps"], "rope_theta": cfg["rope_theta"],
    }.items()))


def hidden(weights, cfg: dict, tokens, cdt=jnp.bfloat16):
    """Final normed hidden states [B, S, d] (float32) of ``tokens`` [B, S],
    one jitted layer at a time."""
    tokens = jnp.asarray(tokens, jnp.int32)
    b, s = tokens.shape
    x = jnp.take(weights["embed"]["table"], tokens, axis=0).astype(cdt)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    key = _cfg_key(cfg)
    for p in weights["layers"]:
        x = _layer(x, p, pos, cfg_key=key, cdt=cdt)
    return _final(x, weights["final_norm"]["scale"], eps=cfg["norm_eps"],
                  cdt=cdt)


@functools.partial(jax.jit, static_argnames=("vocab", "transpose"))
def _head_block(h_ref, h_ctl, w, col0, served, best, at_served, ctl_best,
                ctl_tok, *, vocab, transpose):
    """Fold one block of vocabulary columns into the running per-position
    readings: the reference's best logit, its logit of the served token,
    and the control's argmax token (with its logit, to find it)."""
    with jax.default_matmul_precision("highest"):
        w32 = w.astype(jnp.float32)
        eq = "bsd,vd->bsv" if transpose else "bsd,dv->bsv"
        lr = jnp.einsum(eq, h_ref, w32)
        lc = jnp.einsum(eq, h_ctl, w32)
    cols = col0 + jnp.arange(lr.shape[-1])
    real = cols < vocab
    lr = jnp.where(real, lr, -jnp.inf)
    lc = jnp.where(real, lc, -jnp.inf)
    best = jnp.maximum(best, lr.max(-1))
    rel = served - col0
    inside = (rel >= 0) & (rel < lr.shape[-1])
    col = jnp.clip(rel, 0, lr.shape[-1] - 1)[..., None]
    got = jnp.take_along_axis(lr, col, axis=-1)[..., 0]
    at_served = jnp.where(inside, got, at_served)
    cb, ci = lc.max(-1), lc.argmax(-1)
    take = cb > ctl_best
    ctl_tok = jnp.where(take, col0 + ci, ctl_tok)
    return best, at_served, jnp.maximum(ctl_best, cb), ctl_tok


@functools.partial(jax.jit, static_argnames=("transpose",))
def _head_at(h_ref, w, tok, *, transpose):
    """The reference's logit of token ``tok`` [B, S] at each position."""
    rows = w[tok] if transpose else jnp.moveaxis(w[:, tok], 0, -1)
    with jax.default_matmul_precision("highest"):
        return jnp.sum(h_ref * rows.astype(jnp.float32), axis=-1)


def readings(weights, cfg: dict, tokens, served, control_cdt=None):
    """Per-position readings over ``tokens`` [B, S] (prompt then served
    tokens): ``best`` and ``at_served`` are the reference's best logit and
    its logit of ``served`` [B, S] (the token the program served at that
    position, -1 where none); with ``control_cdt`` also ``control_at``,
    the reference's logit of the token the control puts first.  All
    [B, S] float32 numpy."""
    h_ref = hidden(weights, cfg, tokens)
    h_ctl = h_ref if control_cdt is None else hidden(weights, cfg, tokens,
                                                     control_cdt)
    tied = cfg["tie_embeddings"]
    w = weights["embed"]["table"] if tied else weights["lm_head"]["kernel"]
    n_cols = w.shape[0] if tied else w.shape[1]
    b, s = np.shape(tokens)
    served = jnp.asarray(served, jnp.int32)
    best = jnp.full((b, s), -jnp.inf, jnp.float32)
    at_served = jnp.full((b, s), -jnp.inf, jnp.float32)
    ctl_best = jnp.full((b, s), -jnp.inf, jnp.float32)
    ctl_tok = jnp.zeros((b, s), jnp.int32)
    for c0 in range(0, n_cols, V_BLOCK):
        blk = w[c0:c0 + V_BLOCK] if tied else w[:, c0:c0 + V_BLOCK]
        best, at_served, ctl_best, ctl_tok = _head_block(
            h_ref, h_ctl, blk, jnp.int32(c0), served, best, at_served,
            ctl_best, ctl_tok, vocab=cfg["vocab_size"], transpose=tied)
    out = {"best": np.asarray(best), "at_served": np.asarray(at_served)}
    if control_cdt is not None:
        out["control_at"] = np.asarray(
            _head_at(h_ref, w, ctl_tok, transpose=tied))
    return out
