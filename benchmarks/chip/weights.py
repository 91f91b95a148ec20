"""Seeded random weights for a configuration, in the tree the serving engine
takes (``lm.init_params`` layout), made on the device in one jitted call.

Each 2-D projection carries ``w_step`` = absmax of its kernel (the W2
lattice ``{-2, -1, 0, 1} * w_step``) and ``a_step`` = 1/sqrt(qmax_a), the
activation step the program's own LSQ init uses.  The output projections
(``attn/o``, ``mlp/down``) are drawn with ``residual_scale`` / sqrt(d_in),
as GPT-2 scales the projections that write into the residual stream; the
embedding with ``embed_std``.  Both come from the configuration file.

The reference (reference.py) reads these same float weights and quantizes
them itself; nothing here comes from the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def padded_vocab(cfg: dict) -> int:
    return -(-cfg["vocab_size"] // 256) * 256


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["d_model"] // cfg["num_heads"]


def projection_shapes(cfg: dict) -> dict:
    """{name: (d_in, d_out, is_output_projection)} of one decoder layer."""
    d, hd = cfg["d_model"], head_dim(cfg)
    h, kvh, ff = cfg["num_heads"], cfg["num_kv_heads"], cfg["d_ff"]
    return {"attn/q": (d, h * hd, False), "attn/k": (d, kvh * hd, False),
            "attn/v": (d, kvh * hd, False), "attn/o": (h * hd, d, True),
            "mlp/up": (d, ff, False), "mlp/gate": (d, ff, False),
            "mlp/down": (ff, d, True)}


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds may exceed 32 bits): the
    low 32 bits seed it, the high 32 are folded in.  The key is RBG (the
    chip's own random-bit generator), because threefry took about 36 s on
    a v5e to draw stablelm's 1.6 G weights."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _key_tuple(cfg: dict) -> tuple:
    keys = ("num_layers", "d_model", "num_heads", "num_kv_heads", "d_ff",
            "vocab_size", "head_dim", "tie_embeddings", "a_bits")
    init = cfg["init"]
    return tuple(cfg.get(k) for k in keys) + (init["embed_std"],
                                              init["residual_scale"])


@functools.lru_cache(maxsize=None)
def _builder(key_tuple):
    (n_layers, d, _h, _kvh, _ff, _v, _hd, tied, a_bits,
     embed_std, residual_scale) = key_tuple
    cfg = dict(zip(("num_layers", "d_model", "num_heads", "num_kv_heads",
                    "d_ff", "vocab_size", "head_dim"), key_tuple[:7]))
    shapes = projection_shapes(cfg)
    vocab = padded_vocab(cfg)
    a_step = np.float32(1.0 / np.sqrt((1 << a_bits) - 1))

    def proj(key, d_in, d_out, out_proj):
        std = (residual_scale if out_proj else 1.0) / np.sqrt(d_in)
        kernel = (jax.random.normal(key, (d_in, d_out), jnp.float32)
                  * std).astype(jnp.bfloat16)
        w_step = jnp.maximum(jnp.max(jnp.abs(kernel.astype(jnp.float32))),
                             1e-8)
        return {"kernel": kernel, "w_step": w_step,
                "a_step": jnp.asarray(a_step)}

    def layer(key):
        ks = jax.random.split(key, len(shapes))
        out = {"norm1": {"scale": jnp.ones((d,), jnp.bfloat16)},
               "norm2": {"scale": jnp.ones((d,), jnp.bfloat16)},
               "attn": {}, "mlp": {}}
        for k, (name, (d_in, d_out, o)) in zip(ks, shapes.items()):
            grp, leaf = name.split("/")
            out[grp][leaf] = proj(k, d_in, d_out, o)
        return out

    def build(key):
        ks = jax.random.split(key, n_layers + 2)
        p = {"embed": {"table": (jax.random.normal(ks[0], (vocab, d),
                                                   jnp.float32)
                                 * embed_std).astype(jnp.bfloat16)},
             "layers": [layer(ks[2 + i]) for i in range(n_layers)],
             "final_norm": {"scale": jnp.ones((d,), jnp.bfloat16)}}
        if not tied:
            p["lm_head"] = {"kernel": (jax.random.normal(
                ks[1], (d, vocab), jnp.float32)
                / np.sqrt(d)).astype(jnp.bfloat16)}
        return p

    return jax.jit(build)


def make_weights(cfg: dict, seed: int):
    """The configuration's float weights from ``seed`` (bf16 on device)."""
    return _builder(_key_tuple(cfg))(seed_key(seed))

