"""Operations and bytes the algorithm needs, from shapes and nominal bit
widths alone (never from the stored lane layout), so a share of the
roofline reads the same work whatever implements it.

Operations count a multiply and an add as two.  Bytes count each operand
read once and each result written once, at its nominal width: weights at
``w_bits``, packed activations at ``a_bits``, the KV cache at ``kv_bits``
plus its bfloat16 step per (position, kv head), float activations and
outputs at their own width.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Work:
    int_ops: float = 0.0      # integer (int8-peak) operations
    float_ops: float = 0.0    # bfloat16-peak operations
    bytes: float = 0.0

    def __add__(self, other: "Work") -> "Work":
        return Work(self.int_ops + other.int_ops,
                    self.float_ops + other.float_ops,
                    self.bytes + other.bytes)

    def scaled(self, k: float) -> "Work":
        return Work(self.int_ops * k, self.float_ops * k, self.bytes * k)

    def min_seconds(self, peaks: dict) -> tuple[float, str]:
        """The least time on the chip and which bound sets it."""
        compute = (self.int_ops / peaks["int8_ops"]
                   + self.float_ops / peaks["bf16_flops"])
        memory = self.bytes / peaks["hbm_bytes_s"]
        return (compute, "compute") if compute >= memory \
            else (memory, "bytes")


def packed_matmul(m: int, k: int, n: int, w_bits: int, a_bits: int) -> Work:
    """One W{w}A{a} matmul call: [m, k] lattice x [k, n] lattice -> int32."""
    return Work(int_ops=2.0 * m * k * n,
                bytes=k * n * w_bits / 8 + m * k * a_bits / 8 + m * n * 4)


def decode_attention(contexts, heads: int, kv_heads: int, hd: int,
                     kv_bits: int) -> Work:
    """One decode-attention call over rows with ``contexts`` cached tokens
    each (one query row per sequence): scores and the weighted sum of
    values for every query head, reading K and V once per kv head."""
    ctx = float(sum(contexts))
    rows = len(contexts)
    kv = ctx * kv_heads * (2 * hd * kv_bits / 8 + 2 * 2)
    qo = rows * heads * hd * (2 + 2)
    return Work(float_ops=4.0 * ctx * heads * hd, bytes=kv + qo)


def layer_projections(cfg: dict) -> list[tuple[int, int]]:
    """(k, n) of each packed projection of one decoder layer."""
    d = cfg["d_model"]
    hd = cfg.get("head_dim") or d // cfg["num_heads"]
    h, kvh, ff = cfg["num_heads"], cfg["num_kv_heads"], cfg["d_ff"]
    return [(d, h * hd), (d, kvh * hd), (d, kvh * hd), (h * hd, d),
            (d, ff), (d, ff), (ff, d)]


def layers_work(cfg: dict, n_tokens: int, positions_sum: float) -> Work:
    """Every layer's work for ``n_tokens`` tokens whose positions (tokens
    before each) add up to ``positions_sum``: the packed projections, and
    attention over each token's context and itself."""
    d = cfg["d_model"]
    hd = cfg.get("head_dim") or d // cfg["num_heads"]
    layers = cfg["num_layers"]
    proj = sum(2.0 * k * n for k, n in layer_projections(cfg))
    attn = 4.0 * cfg["num_heads"] * hd * (positions_sum + n_tokens)
    return Work(int_ops=layers * proj * n_tokens, float_ops=layers * attn)


def head_work(cfg: dict, rows: int) -> Work:
    """The output head over ``rows`` logits rows (bfloat16 matmul)."""
    return Work(float_ops=2.0 * cfg["d_model"] * cfg["vocab_size"] * rows)


def token_work(cfg: dict, context: int) -> Work:
    """Model work of one generated token at position ``context``."""
    return layers_work(cfg, 1, context) + head_work(cfg, 1)
