"""Fused runtime quantize+pack Pallas kernel.

The paper measures activation packing *at runtime* as part of conv2d cost
(§V-A).  On TPU we fuse quantization (affine lattice), P1 packing and the
zero-point row-sum reduction into a single VMEM pass so the packed operand is
produced in one read of the activation tensor.  Emits:
  packed  [M, K/n_pack]  lane dtype
  row_sum [M, 1]         s32   (sum_k q_a — for the affine correction)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import PackSpec
from repro.kernels import plan as plan_lib


def _kernel(x_ref, s_ref, z_ref, packed_ref, rs_ref, rs_acc,
            *, spec: PackSpec):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        rs_acc[...] = jnp.zeros_like(rs_acc)

    scale = s_ref[0, 0]
    zp = z_ref[0, 0]
    qmax = (1 << spec.a_bits) - 1
    x = x_ref[...].astype(jnp.float32)
    q = jnp.clip(jnp.round(x / scale) + zp, 0, qmax).astype(jnp.int32)
    rs_acc[...] += jnp.sum(q, axis=1, keepdims=True)
    # De-interleave on the MXU: field j of lane i is column n_pack*i + j,
    # picked out by an exact 0/1 selection matmul (Mosaic lowers neither
    # the [bm, kp, n_pack] reshape nor lane-strided reads).  8-bit fields
    # are recentred into int8 and shifted back after the selection.
    bm, bk = q.shape
    kp = bk // spec.n_pack
    off = 128 if spec.a_bits == 8 else 0
    q8 = (q - off).astype(jnp.int8)
    rows = jax.lax.broadcasted_iota(jnp.int32, (bk, kp), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bk, kp), 1)
    packed = jnp.zeros((bm, kp), jnp.int32)
    for j in range(spec.n_pack):
        sel = (rows == cols * spec.n_pack + j).astype(jnp.int8)
        field = jax.lax.dot_general(q8, sel, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.int32)
        packed = packed + ((field + off) << (spec.shift * j))
    packed_ref[...] = packed.astype(spec.lane_dtype)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _done():
        rs_ref[...] = rs_acc[...]


def _pad_axis(x, axis, multiple):
    rem = (-x.shape[axis]) % multiple
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


@functools.partial(
    jax.jit, static_argnames=("spec", "block_m", "block_k", "interpret"))
def quantize_pack(x: jax.Array, scale: jax.Array, zero_point: jax.Array,
                  spec: PackSpec, *, block_m: int = 256, block_k: int = 512,
                  interpret: bool | None = None):
    """Quantize to the a_bits lattice and P1-pack along the last axis.

    ``interpret`` defaults from plan.default_interpret(): interpreter on CPU
    (validation mode), compiled on TPU.
    """
    if interpret is None:
        interpret = plan_lib.default_interpret()
    m, k = x.shape
    block_k = max(spec.n_pack, block_k - block_k % spec.n_pack)
    x_p = _pad_axis(_pad_axis(x, 0, block_m), 1, block_k)
    # NOTE: padding rows/cols quantize to q = clip(round(0/s)+zp) = zp, which
    # would corrupt row sums for padded COLUMNS of real rows -> mask them by
    # padding with the dequantized zero so q == zp... instead we pad x with
    # scale*(-zp) so q == 0 exactly.
    if x_p.shape != (m, k):
        fill = -scale * zero_point.astype(jnp.float32)
        mask = jnp.zeros(x_p.shape, bool).at[:m, :k].set(True)
        x_p = jnp.where(mask, x_p, fill)
    s = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    z = jnp.asarray(zero_point, jnp.int32).reshape(1, 1)
    gm = x_p.shape[0] // block_m
    gk = x_p.shape[1] // block_k
    kp_block = block_k // spec.n_pack

    packed, row_sum = pl.pallas_call(
        functools.partial(_kernel, spec=spec),
        grid=(gm, gk),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, kk: (i, kk)),
            pl.BlockSpec((1, 1), lambda i, kk: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda i, kk: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((block_m, kp_block), lambda i, kk: (i, kk)),
            pl.BlockSpec((block_m, 1), lambda i, kk: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((x_p.shape[0], x_p.shape[1] // spec.n_pack),
                                 spec.lane_dtype),
            jax.ShapeDtypeStruct((x_p.shape[0], 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((block_m, 1), jnp.int32)],
        interpret=interpret, name="quantize_pack",
    )(x_p, s, z)
    kp = -(-k // spec.n_pack)
    return packed[:m, :kp], row_sum[:m]
