"""Functional NN substrate: Dense (float / QAT / packed-integer), norms,
embeddings, RoPE (incl. M-RoPE).

Parameters are plain nested dicts; every layer is an (init, apply) pair.
``quant_mode``:
  'none'   — float path.
  'qat'    — LSQ fake-quant on weights+activations (training; STE grads).
  'packed' — deployed Sparq path: runtime activation quantize+pack, packed
             integer matmul, affine dequant.  Params must have been converted
             with ``pack_dense_params``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.core.packing import PackSpec
from repro.core.quant import QuantConfig
from repro.core.reduce import ordered_sum
from repro.kernels import ops


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# Dense
# ---------------------------------------------------------------------------

def dense_init(key, d_in, d_out, *, use_bias=False, dtype=jnp.float32,
               quantized=False, qcfg: QuantConfig | None = None, scale=None):
    std = scale if scale is not None else 1.0 / np.sqrt(d_in)
    kernel = jax.random.normal(key, (d_in, d_out), jnp.float32) * std
    p = {"kernel": kernel.astype(dtype)}
    if use_bias:
        p["bias"] = jnp.zeros((d_out,), dtype)
    if quantized and qcfg is not None and qcfg.enabled:
        p["w_step"] = quant.init_step_from_data(kernel, qcfg.w_bits, True)
        p["a_step"] = jnp.asarray(1.0 / np.sqrt(qcfg.qmax_a), jnp.float32)
    return p


def dense_layer_spec(k: int, n: int, qcfg: QuantConfig, *,
                     weight_store: str = "lanes",
                     w_packed=None) -> PackSpec:
    """The per-layer *chosen* lane layout for a [k, n] Dense (DESIGN.md §16).

    Resolves through the active autotune layout cache (autotune.
    matmul_layout_for), defaulting to the config-global spec on a miss, so
    pack time, plan time and dispatch time all agree on one layout.  With
    the lanes store the packed leaf (``w_packed``) is evidence of the layout
    the stored bytes actually use: if the cache changed since pack time and
    the chosen layout no longer matches the leaf's dtype/shape, fall back to
    the config spec rather than misread the bytes.  (Bit-dense words are
    layout-agnostic at rest, so the dense store never needs this guard.)
    """
    from repro.kernels import autotune

    base = PackSpec.from_config(qcfg)
    spec = autotune.matmul_layout_for(k, n, base, backend="auto",
                                      weight_store=weight_store)
    if weight_store == "lanes" and w_packed is not None and spec != base:
        if (w_packed.dtype != spec.lane_dtype
                or w_packed.shape[0] != -(-k // spec.n_pack)):
            return base
    return spec


def dense_apply(p, x, *, qcfg: QuantConfig | None = None,
                quant_mode: str = "none", compute_dtype=jnp.bfloat16):
    """y = x @ kernel (+ bias), under the selected quantization mode."""
    quantized = qcfg is not None and qcfg.enabled and "w_step" in p \
        or (qcfg is not None and qcfg.enabled and "w_packed" in p)
    if quant_mode == "packed" and ("w_packed" in p or "w_dense" in p):
        dense = "w_dense" in p
        w = p["w_dense"] if dense else p["w_packed"]
        spec = dense_layer_spec(
            int(x.shape[-1]), int(w.shape[-1]), qcfg,
            weight_store="dense" if dense else "lanes",
            w_packed=None if dense else w)
        return ops.quantized_linear(
            x.astype(jnp.float32), w,
            p["col_sums"], p["a_scale"], p["a_zp"], p["w_scale"], p["w_zp"],
            spec, bias=p.get("bias"), backend="auto",
            weight_store="dense" if dense else "lanes",
            out_dtype=compute_dtype)
    kernel = p["kernel"].astype(compute_dtype)
    if quant_mode == "qat" and quantized and "w_step" in p:
        # weights fake-quant in f32 (few, precision-sensitive); activations
        # fake-quant in compute dtype — the lattice (<= 2^bits) is exactly
        # representable in bf16, and this halves the activation temp/traffic
        kernel = quant.lsq_fake_quant(
            p["kernel"].astype(jnp.float32), p["w_step"], qcfg.w_bits,
            True).astype(compute_dtype)
        x = quant.lsq_fake_quant(
            x.astype(compute_dtype), p["a_step"].astype(compute_dtype),
            qcfg.a_bits, True)
    y = jnp.dot(x.astype(compute_dtype), kernel)
    if "bias" in p:
        y = y + p["bias"].astype(compute_dtype)
    return y


def pack_dense_params(p, qcfg: QuantConfig, *, dense_store: bool = False,
                      spec: PackSpec | None = None):
    """Offline conversion QAT/float Dense params -> deployed packed params.

    ``dense_store=True`` keeps the weight bit-dense (int32 words, true
    w_bits/value HBM footprint; key ``w_dense``) instead of as P1 lanes —
    the decode memory-bound path; lanes are recovered at use.

    The lane layout is the per-layer chosen spec (``dense_layer_spec``:
    active layout cache, config default on miss) unless pinned via ``spec``
    — weights pack once offline, so the layout decision happens here and
    dispatch resolves the same choice.
    """
    kernel = p["kernel"].astype(jnp.float32)
    store = "dense" if dense_store else "lanes"
    if spec is None:
        spec = dense_layer_spec(int(kernel.shape[0]), int(kernel.shape[1]),
                                qcfg, weight_store=store)
    w_scale = p.get("w_step")
    if w_scale is None:
        w_scale, _ = quant.calibrate_absmax(kernel, qcfg.w_bits)
    w_zp = jnp.int32(qcfg.w_zero_point)
    w_packed, col_sums = ops.prepare_weights(kernel, w_scale, w_zp, spec,
                                             weight_store=store)
    a_scale = p.get("a_step", jnp.float32(1.0 / np.sqrt(qcfg.qmax_a)))
    a_zp = jnp.int32((qcfg.qmax_a + 1) // 2)
    # Packing rounds K up (words and lanes both); record the exact K so
    # offline plan building and layout resolution key the same (k, n) the
    # dispatch path derives from x.shape.
    out = {"w_dense" if dense_store else "w_packed": w_packed,
           "col_sums": col_sums,
           "w_scale": jnp.asarray(w_scale, jnp.float32), "w_zp": w_zp,
           "a_scale": jnp.asarray(a_scale, jnp.float32), "a_zp": a_zp,
           "k_full": int(kernel.shape[0])}
    if "bias" in p:
        out["bias"] = p["bias"]
    return out


# ---------------------------------------------------------------------------
# Norms & embedding
# ---------------------------------------------------------------------------

def rmsnorm_init(d, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm_apply(p, x, eps=1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    # fixed-order sum: a row normalizes the same in every window shape
    var = ordered_sum(x32 * x32, keepdims=True) / x.shape[-1]
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(dt)


def layernorm_init(d, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm_apply(p, x, eps=1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(dt)


def embedding_init(key, vocab, d, dtype=jnp.float32):
    return {"table": (jax.random.normal(key, (vocab, d), jnp.float32)
                      * 0.02).astype(dtype)}


def embedding_apply(p, tokens, compute_dtype=jnp.bfloat16):
    """Embedding lookup.

    Under a production mesh the table is vocab-sharded over 'model'; a plain
    gather there makes XLA SPMD replicate the table per use (and hits a
    partitioner verifier bug inside scan bodies), so we do the standard
    sharded-vocab lookup manually: shard_map -> masked local gather -> psum.
    Outside a mesh this is a plain take().
    """
    from repro.parallel import sharding as shlib
    mesh = shlib._ACTIVE_MESH[-1]
    table = p["table"]
    if mesh is None or "model" not in mesh.shape \
            or table.shape[0] % mesh.shape["model"] != 0:
        return jnp.take(table, tokens, axis=0).astype(compute_dtype)

    from jax.sharding import PartitionSpec as P

    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    bspec = dp if dp and tokens.shape[0] % shlib._axis_size(mesh, dp) == 0 \
        else None

    def local(tab, tok):
        idx = jax.lax.axis_index("model")
        vloc = tab.shape[0]
        rel = tok - idx * vloc
        ok = (rel >= 0) & (rel < vloc)
        safe = jnp.clip(rel, 0, vloc - 1)
        emb = jnp.take(tab, safe, axis=0).astype(compute_dtype)
        emb = emb * ok[..., None].astype(compute_dtype)
        return jax.lax.psum(emb, "model")

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("model", None), P(bspec, None)),
        out_specs=P(bspec, None, None),
        check_vma=False)(table, tokens)


def embedding_attend(p, x):
    """Tied LM head: x [.., d] @ table.T -> [.., vocab]."""
    return jnp.dot(x, p["table"].astype(x.dtype).T)


# ---------------------------------------------------------------------------
# RoPE (standard + M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim, theta):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta=10000.0):
    """x: [B, S, H, hd]; positions: [B, S] int."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                      # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,hd/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, sections, theta=10000.0):
    """Multimodal RoPE (qwen2-vl §2): positions3 [3, B, S] = (t, h, w) ids;
    frequency channels are split between the three components."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = rope_freqs(hd, theta)                      # [half]
    sec = np.cumsum((0,) + tuple(sections))
    assert sec[-1] == half, (sections, half)
    comp = np.zeros((half,), np.int32)
    for i in range(3):
        comp[sec[i]:sec[i + 1]] = i
    comp = jnp.asarray(comp)
    pos = jnp.take(positions3, comp, axis=0)           # [half, B, S]
    angles = jnp.moveaxis(pos, 0, -1).astype(jnp.float32) * freqs  # [B,S,half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)
