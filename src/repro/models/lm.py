"""Model assembly: heterogeneous decoder stacks (attn / mamba / mLSTM /
sLSTM blocks, MoE or dense FFN halves), encoder-decoder, modality-frontend
stubs, LM head and loss.

Batch protocols (matching launch/input_specs):
  dense/moe/ssm/hybrid : {"tokens": [B,S], "labels": [B,S]}
  vlm (qwen2-vl)       : + {"embeds": [B,S_img,fd], "positions3": [3,B,S]}
  audio enc-dec        : {"enc_embeds": [B,S_enc,fd], "tokens": [B,S_dec], ...}
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention, common, mamba, mlp, moe, xlstm
from repro.parallel.sharding import constrain
from repro.models.common import dense_apply, dense_init


# ---------------------------------------------------------------------------
# Block init / apply
# ---------------------------------------------------------------------------

def block_init(key, cfg, i, *, cross=False, dtype=jnp.float32):
    kind = cfg.layer_kind(i)
    ks = jax.random.split(key, 6)
    p = {}
    if kind == "attn":
        p["norm1"] = common.rmsnorm_init(cfg.d_model, dtype)
        p["attn"] = attention.attention_init(ks[0], cfg, dtype=dtype)
    elif kind == "mamba":
        p["norm1"] = common.rmsnorm_init(cfg.d_model, dtype)
        p["mamba"] = mamba.mamba_init(ks[0], cfg, dtype=dtype)
    elif kind == "mlstm":
        p["norm1"] = common.rmsnorm_init(cfg.d_model, dtype)
        p["mlstm"] = xlstm.mlstm_init(ks[0], cfg, dtype=dtype)
    elif kind == "slstm":
        p["norm1"] = common.rmsnorm_init(cfg.d_model, dtype)
        p["slstm"] = xlstm.slstm_init(ks[0], cfg, dtype=dtype)
    if cross:
        p["norm_x"] = common.rmsnorm_init(cfg.d_model, dtype)
        p["cross"] = attention.attention_init(ks[1], cfg, cross=True,
                                              dtype=dtype)
    # FFN half (attn/mamba families; xLSTM blocks are single-residual)
    if kind in ("attn", "mamba") and (cfg.d_ff or cfg.layer_is_moe(i)):
        p["norm2"] = common.rmsnorm_init(cfg.d_model, dtype)
        if cfg.layer_is_moe(i):
            p["moe"] = moe.moe_init(ks[2], cfg, dtype=dtype)
        else:
            p["mlp"] = mlp.mlp_init(ks[2], cfg, dtype=dtype)
    return p


def block_apply(p, cfg, x, *, kind="attn", positions, quant_mode="none",
                cache=None, cache_index=None, cache_valid=None, causal=True,
                positions3=None, enc_kv=None, moe_path="einsum",
                kv_shard_axis=None, block_tables=None):
    """One residual block.  Returns (x, new_cache, aux_loss).

    ``cache_index`` may be a scalar (lockstep decode) or a [B] vector of
    per-slot write offsets; ``cache_valid`` [B] counts each row's valid-
    prefix tokens for ragged windows (DESIGN.md §12).  ``kv_shard_axis``
    names the mesh axis a serving ShardPlan sharded the KV-cache kv-head
    axis over (DESIGN.md §15); None = unsharded serving.  ``block_tables``
    [B, n_pages] selects the paged attention cache path (pool + per-slot
    block table, DESIGN.md §18); recurrent sub-caches stay per-slot.

    Each half runs under a ``jax.named_scope`` named by its kind (``attn``,
    ``mamba``, ``mlstm``, ``slstm``, ``cross``, ``mlp``, ``moe``), so a
    profiler trace attributes every device op to the half that emits it.
    """
    aux = 0.0
    new_cache = dict(cache) if cache is not None else None
    with jax.named_scope(kind):
        h = common.rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
        if kind == "attn":
            sub = cache.get("attn") if cache else None
            out, sub2 = attention.attention_apply(
                p["attn"], cfg, h, positions=positions,
                quant_mode=quant_mode, cache=sub, cache_index=cache_index,
                cache_valid=cache_valid, causal=causal,
                positions3=positions3, kv_shard_axis=kv_shard_axis,
                block_tables=block_tables)
        elif kind == "mamba":
            sub = cache.get("mamba") if cache else None
            out, sub2 = mamba.mamba_apply(
                p["mamba"], cfg, h, quant_mode=quant_mode, cache=sub,
                cache_index=cache_index, cache_valid=cache_valid)
        elif kind == "mlstm":
            sub = cache.get("mlstm") if cache else None
            out, sub2 = xlstm.mlstm_apply(
                p["mlstm"], cfg, h, quant_mode=quant_mode, cache=sub,
                cache_index=cache_index, cache_valid=cache_valid)
        elif kind == "slstm":
            sub = cache.get("slstm") if cache else None
            out, sub2 = xlstm.slstm_apply(
                p["slstm"], cfg, h, quant_mode=quant_mode, cache=sub,
                cache_index=cache_index, cache_valid=cache_valid)
        else:
            raise ValueError(kind)
        if new_cache is not None and sub2 is not None:
            new_cache[kind] = sub2
        x = x + out

    if "cross" in p and enc_kv is not None:
        with jax.named_scope("cross"):
            h = common.rmsnorm_apply(p["norm_x"], x, cfg.norm_eps)
            out, _ = attention.attention_apply(
                p["cross"], cfg, h, positions=positions,
                quant_mode=quant_mode, cross_kv=enc_kv, causal=False)
            x = x + out

    if "moe" in p:
        with jax.named_scope("moe"):
            h = common.rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
            out, aux = moe.moe_apply(p["moe"], cfg, h,
                                     quant_mode=quant_mode, path=moe_path)
            x = x + out
    elif "mlp" in p:
        with jax.named_scope("mlp"):
            h = common.rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
            x = x + mlp.mlp_apply(p["mlp"], cfg, h, quant_mode=quant_mode)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------

def init_params(key, cfg):
    dtype = common.dtype_of(cfg.param_dtype)
    keys = jax.random.split(key, cfg.num_layers + cfg.encoder_layers + 4)
    p = {"embed": common.embedding_init(keys[0], cfg.padded_vocab,
                                        cfg.d_model, dtype)}
    cross = cfg.is_encoder_decoder
    p["layers"] = [
        block_init(keys[1 + i], cfg, i, cross=cross, dtype=dtype)
        for i in range(cfg.num_layers)]
    p["final_norm"] = common.rmsnorm_init(cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(
            keys[cfg.num_layers + 1], cfg.d_model, cfg.padded_vocab,
            dtype=dtype, quantized=cfg.quant.quantize_lm_head,
            qcfg=cfg.quant)
    if cfg.is_encoder_decoder:
        enc_cfg = cfg  # same dims; encoder is non-causal full attention
        p["encoder"] = {
            "layers": [block_init(keys[cfg.num_layers + 2 + i], enc_cfg, i,
                                  dtype=dtype)
                       for i in range(cfg.encoder_layers)],
            "final_norm": common.rmsnorm_init(cfg.d_model, dtype),
        }
    if cfg.frontend != "none":
        p["frontend_proj"] = dense_init(
            keys[-1], cfg.frontend_dim, cfg.d_model, dtype=dtype)
    return p


def encode(params, cfg, enc_embeds, *, quant_mode="none"):
    """Encoder over stub modality embeddings -> memory states [B,S,d]."""
    cd = common.dtype_of(cfg.compute_dtype)
    x = dense_apply(params["frontend_proj"], enc_embeds.astype(cd),
                    compute_dtype=cd)
    pos = jnp.arange(x.shape[1])[None, :]
    pos = jnp.broadcast_to(pos, x.shape[:2])
    for blk in params["encoder"]["layers"]:
        x, _, _ = block_apply(blk, cfg, x, kind="attn", positions=pos,
                              quant_mode=quant_mode, causal=False)
    return common.rmsnorm_apply(params["encoder"]["final_norm"], x,
                                cfg.norm_eps)


def _decoder_inputs(params, cfg, batch):
    """Token (+ modality prefix) embeddings and positions."""
    cd = common.dtype_of(cfg.compute_dtype)
    x = common.embedding_apply(params["embed"], batch["tokens"], cd)
    if cfg.frontend == "vision" and "embeds" in batch:
        prefix = dense_apply(params["frontend_proj"],
                             batch["embeds"].astype(cd), compute_dtype=cd)
        x = jnp.concatenate([prefix, x], axis=1)
    b, s = x.shape[0], x.shape[1]
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    return x, positions


def forward(params, cfg, batch, *, quant_mode="none", caches=None,
            cache_index=None, cache_valid=None, enc_out=None, remat=False,
            moe_path="einsum", kv_shard_axis=None, block_tables=None):
    """Full forward.  Returns (logits, aux_loss, new_caches).

    ``cache_index`` scalar = lockstep decode; [B] vector = per-slot cache
    write offsets (ragged continuous batching).  ``cache_valid`` [B] is the
    per-row valid-prefix length of the current window (chunked prefill).
    ``kv_shard_axis`` (serving TP, DESIGN.md §15) pins attention's KV-cache
    quantize/pack/write to the kv-head shard axis so GSPMD never reshards
    the cache between steps.  ``block_tables`` [B, n_pages] routes every
    attention layer through the paged cache pool (DESIGN.md §18); the one
    table indexes all layers' pools.

    Device scopes (``jax.named_scope``): ``embed``, ``layer_<i>`` per block
    (block_apply names its halves), ``head`` for the final norm, the output
    head and the pad bias.
    """
    import os
    seq_ax = "model" if os.environ.get("REPRO_SEQ_ACT", "0") == "1" \
        else None
    with jax.named_scope("embed"):
        x, positions = _decoder_inputs(params, cfg, batch)
        x = constrain(x, "dp", seq_ax, None)
    positions3 = batch.get("positions3")

    enc_kv = None
    if cfg.is_encoder_decoder:
        if enc_out is None and "enc_embeds" in batch:
            enc_out = encode(params, cfg, batch["enc_embeds"],
                             quant_mode=quant_mode)

    aux_total = 0.0
    new_caches = [] if caches is not None else None

    def run_block(blk, x, sub, kind):
        return block_apply(
            blk, cfg, x, kind=kind, positions=positions,
            quant_mode=quant_mode, cache=sub, cache_index=cache_index,
            cache_valid=cache_valid, causal=True, positions3=positions3,
            enc_kv=enc_kv, moe_path=moe_path, kv_shard_axis=kv_shard_axis,
            block_tables=block_tables)

    for li, blk in enumerate(params["layers"]):
        if cfg.is_encoder_decoder:
            cached_kv = caches[li].get("cross_kv") if caches is not None \
                else None
            if cached_kv is not None:
                enc_kv = cached_kv
            elif enc_out is not None:
                enc_kv = attention.precompute_cross_kv(
                    blk["cross"], cfg, enc_out, quant_mode=quant_mode)
        sub = caches[li] if caches is not None else None
        fn = jax.checkpoint(run_block, static_argnums=(3,)) if remat \
            else run_block
        with jax.named_scope(f"layer_{li}"):
            x, sub2, aux = fn(blk, x, sub, cfg.layer_kind(li))
            # Megatron-SP (REPRO_SEQ_ACT=1): residual stream sequence-
            # sharded over the TP axis between blocks -> the TP all-reduce
            # becomes a reduce-scatter + all-gather pair (half the wire
            # bytes) and norms run seq-sharded (§Perf cell B)
            x = constrain(x, "dp", seq_ax, None)
        aux_total = aux_total + aux
        if new_caches is not None:
            if cfg.is_encoder_decoder and enc_kv is not None:
                sub2 = dict(sub2 or {})
                sub2["cross_kv"] = enc_kv
            new_caches.append(sub2)

    with jax.named_scope("head"):
        x = common.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = common.embedding_attend(params["embed"], x)
        else:
            logits = dense_apply(
                params["lm_head"], x,
                qcfg=cfg.quant if cfg.quant.quantize_lm_head else None,
                quant_mode=quant_mode,
                compute_dtype=common.dtype_of(cfg.compute_dtype))
        logits = constrain(logits, "dp", None, "model")
        if cfg.padded_vocab != cfg.vocab_size:
            # additive pad bias (fuses into the head matmul epilogue)
            # instead of a where() over an f32 copy — §Perf cell-A iter. 4
            pad_bias = jnp.where(
                jnp.arange(cfg.padded_vocab) >= cfg.vocab_size, -1e30,
                0.0).astype(logits.dtype)
            logits = logits + pad_bias
    return logits, aux_total, new_caches


def init_caches(cfg, batch_size, max_len, dtype=jnp.bfloat16, *,
                page_size=None, num_pages=None):
    """Per-layer decode caches sized for max_len (ring-bounded for SWA).

    With ``page_size``/``num_pages`` the attention caches are paged pools
    ([num_pages, page_size, KVH, ...], one shared page-id space across
    layers, DESIGN.md §18) instead of slot-contiguous rings; recurrent
    sub-caches (mamba/xLSTM) keep their ``batch_size`` slot rows either
    way — only attention KV pages."""
    paged = num_pages is not None
    if paged and page_size is None:
        raise ValueError("num_pages requires page_size")
    caches = []
    for i in range(cfg.num_layers):
        kind = cfg.layer_kind(i)
        if kind == "attn":
            c = {"attn": attention.init_paged_kv_cache(
                cfg, num_pages, page_size, dtype) if paged
                else attention.init_kv_cache(cfg, batch_size, max_len,
                                             dtype)}
        elif kind == "mamba":
            c = {"mamba": mamba.init_mamba_cache(cfg, batch_size)}
        elif kind == "mlstm":
            c = {"mlstm": xlstm.init_mlstm_cache(cfg, batch_size)}
        elif kind == "slstm":
            c = {"slstm": xlstm.init_slstm_cache(cfg, batch_size)}
        if cfg.is_encoder_decoder:
            c["cross_kv"] = None
        caches.append(c)
    return caches


def cache_bytes(cfg, batch_size, max_len, dtype=jnp.bfloat16) -> int:
    """HBM bytes of an ``init_caches`` tree, without allocating it.

    Abstract-evals the cache template, so the number tracks whatever layout
    ``cfg.quant.kv_bits`` selects (bf16 / int8 / bit-dense packed words +
    scales) — the per-slot term of the serving engine's HBM admission
    capacity (DESIGN.md §13)."""
    shapes = jax.eval_shape(
        lambda: init_caches(cfg, batch_size, max_len, dtype=dtype))
    return sum(leaf.size * jnp.dtype(leaf.dtype).itemsize
               for leaf in jax.tree.leaves(shapes))


def cache_page_bytes(cfg, page_size, dtype=jnp.bfloat16) -> int:
    """HBM bytes ONE pool page occupies summed across attention layers.

    The paged-serving capacity unit (DESIGN.md §18): the engine's HBM
    budget buys ``budget // cache_page_bytes`` pages.  Abstract-evals a
    one-page pool so the number tracks whatever layout
    ``cfg.quant.kv_bits`` selects (words + scale planes included).
    Recurrent layers contribute nothing — their per-slot states are not
    paged.  Returns 0 for attention-free stacks (the engine rejects
    paging those)."""
    def build():
        return [attention.init_paged_kv_cache(cfg, 1, page_size, dtype)
                for i in range(cfg.num_layers)
                if cfg.layer_kind(i) == "attn"]

    shapes = jax.eval_shape(build)
    return sum(leaf.size * jnp.dtype(leaf.dtype).itemsize
               for leaf in jax.tree.leaves(shapes))


def loss_fn(logits, labels, aux=0.0, aux_weight=0.01):
    """Masked CE (labels < 0 are padding) + MoE load-balance aux."""
    logits = logits.astype(jnp.float32)
    mask = (labels >= 0)
    labels_safe = jnp.maximum(labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels_safe[..., None],
                               axis=-1)[..., 0]
    denom = jnp.maximum(jnp.sum(mask), 1)
    ce = jnp.sum(jnp.where(mask, nll, 0.0)) / denom
    return ce + aux_weight * aux, ce
