"""Packed sub-byte matmul Pallas TPU kernel.

The kernel computes  D[M, N] = sum_k a[M, K] * w[K, N]  from the P1-packed
operands the serving path stores: activation lanes [M, Kp] (ascending
fields, kernels/quant_pack.py) and weight lanes [Kp, N] (field-reversed,
core/packing.pack_weights).  HBM holds the packed lanes; each K-block is
unpacked in VMEM by shift-mask into ``n_pack`` int8 field planes, and every
plane pair is one int8 x int8 -> int32 MXU contraction:

    D = sum_j  field_j(a) @ field_j(w)

The v5e MXU takes int8 operands only (Mosaic refuses int16/int32 dot
operands), so the packed lanes are never themselves an MXU operand here —
the shift-mask that Sparq's ``vmacsr`` applies after the multiply moves to
the unpack before it, and no extraction band or ``k_tile`` bound applies.
The XLA backend (kernels/ops.py) keeps the packed-lane dots with extraction;
both are exact, so they agree bit for bit.

Fields are unsigned lattice values below ``2**bits``.  A field of 8 bits
does not fit int8, so it is recentred by -128 and the product corrected
with the other operand's K-block sums (at most one operand of a feasible
layout is 8 bits wide).

Block layout (output-stationary): grid = (M/bm, N/bn, Kp/bk), k innermost;
acc[bm, bn] s32 lives in VMEM scratch across the k sweep.  On TPU ``bk``
and ``bn`` must be multiples of 128 and ``bm`` of 8; kernels/plan.py emits
only such blocks (DESIGN.md §10).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import PackSpec
from repro.kernels import plan as plan_lib


def _field(x, shift: int, mask: int, bits: int):
    """One unpacked field plane as an int8 MXU operand, and the recentring
    offset it carries (128 for 8-bit fields, else 0)."""
    f = (x >> shift) & mask
    if bits < 8:
        return f.astype(jnp.int8), 0
    return (f - 128).astype(jnp.int8), 128


def packed_dot(a, w, spec: PackSpec):
    """Exact int32 ``a @ w`` of P1 lanes a [R, K] (ascending fields) and
    w [K, N] (field-reversed): one int8 x int8 MXU contraction per field
    plane pair.  Also the inner product of ulppack_conv2d."""
    a = a.astype(jnp.int32)
    w = w.astype(jnp.int32)
    out = None
    for j in range(spec.n_pack):
        a_j, oa = _field(a, spec.shift * j, spec.field_mask, spec.a_bits)
        w_j, ow = _field(w, spec.shift * (spec.n_pack - 1 - j),
                         spec.field_mask, spec.w_bits)
        t = jax.lax.dot_general(a_j, w_j, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.int32)
        # undo a recentring: sum_k (a-oa)(w-ow) with one of oa/ow zero
        if oa:
            t += oa * jnp.sum(w_j.astype(jnp.int32) + ow, axis=0,
                              keepdims=True)
        if ow:
            t += ow * jnp.sum(a_j.astype(jnp.int32) + oa, axis=1,
                              keepdims=True)
        out = t if out is None else out + t
    return out


def _kernel(a_ref, w_ref, o_ref, acc_ref, *, spec: PackSpec):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # [bm, bk] x [bk, bn] packed lanes
    acc_ref[...] += packed_dot(a_ref[...], w_ref[...], spec)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...]


def _pad_axis(x, axis, multiple):
    rem = (-x.shape[axis]) % multiple
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


@functools.partial(
    jax.jit,
    static_argnames=("spec", "block_m", "block_n", "chunks", "interpret"))
def ulppack_matmul(a_packed: jax.Array, w_packed: jax.Array, spec: PackSpec,
                   *, block_m: int = 128, block_n: int = 128,
                   chunks: int = 8,
                   interpret: bool | None = None) -> jax.Array:
    """Packed-lane matmul: [M, Kp] x [Kp, N] -> s32 [M, N] exact dot values.

    ``chunks`` sets the K block: ``chunks * plan.MATMUL_LANES`` packed
    lanes per grid step.  ``interpret`` defaults from
    plan.default_interpret(): the interpreter on CPU (validation mode),
    compiled on TPU.
    VMEM working set per step: see plan.matmul_working_set.
    """
    if interpret is None:
        interpret = plan_lib.default_interpret()
    if not spec.feasible:
        raise ValueError(f"{spec} outside the overflow-free region")
    if a_packed.dtype != spec.lane_dtype or w_packed.dtype != spec.lane_dtype:
        raise TypeError("operands must already be packed to spec.lane_dtype")
    m, kp = a_packed.shape
    kp2, n = w_packed.shape
    assert kp == kp2, (kp, kp2)
    block_k = chunks * plan_lib.MATMUL_LANES

    a_p = _pad_axis(_pad_axis(a_packed, 0, block_m), 1, block_k)
    w_p = _pad_axis(_pad_axis(w_packed, 0, block_k), 1, block_n)
    gm = a_p.shape[0] // block_m
    gk = a_p.shape[1] // block_k
    gn = w_p.shape[1] // block_n

    out = pl.pallas_call(
        functools.partial(_kernel, spec=spec),
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((a_p.shape[0], w_p.shape[1]),
                                       jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        interpret=interpret, name="ulppack_matmul",
    )(a_p, w_p)
    return out[:m, :n]


def _int_kernel(a_ref, w_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret"))
def int_matmul(q_a: jax.Array, q_w: jax.Array, *, block_m: int = 128,
               block_n: int = 128, block_k: int = 512,
               interpret: bool | None = None) -> jax.Array:
    """Unpacked integer matmul kernel (s8/s16 -> s32).

    Baseline kernel: the paper's int16 conv2d counterpart and the W8A8 / out-
    of-region fallback path on TPU.
    """
    if interpret is None:
        interpret = plan_lib.default_interpret()
    m, k = q_a.shape
    _, n = q_w.shape
    a_p = _pad_axis(_pad_axis(q_a, 0, block_m), 1, block_k)
    w_p = _pad_axis(_pad_axis(q_w, 0, block_k), 1, block_n)
    out = pl.pallas_call(
        _int_kernel,
        grid=(a_p.shape[0] // block_m, w_p.shape[1] // block_n,
              a_p.shape[1] // block_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((a_p.shape[0], w_p.shape[1]),
                                       jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        interpret=interpret, name="ulppack_matmul",
    )(a_p, w_p)
    return out[:m, :n]
