"""Packed sub-byte conv2d Pallas kernel (paper §IV-B, Algorithm 1 on TPU).

Output-stationary, channel-packed (ULPPACK P1 over the C axis).  As in
ulppack_matmul, the packed channel lanes are unpacked in VMEM into int8
field planes and every plane pair is one int8 x int8 -> int32 MXU
contraction (the v5e MXU takes int8 operands only).  The paper's
``vslidedown`` input reuse becomes VMEM-resident window slicing: each
(fh, fw) kernel tap is a shifted view of the VMEM input tile — no im2col
materialization in HBM, mirroring the paper's motivation for a dedicated conv
algorithm (§III-A).

Spatial tiling (DESIGN.md §10): grid ``(N, out_H/block_h, Co/block_co)``.
Each grid step loads a halo-overlapped input tile of ``block_h + fh - 1`` rows
(``pl.Element`` indexing on H: consecutive h-tiles advance by ``block_h`` rows
but read ``fh - 1`` shared halo rows), so VMEM use is bounded by the tile —
not the image — and large-resolution inference stays feasible.  ``block_h``
is chosen offline by kernels/plan.py against the VMEM budget.

Weight storage (``weight_store``):
  'lanes' — w is [Fh, Fw, Cp, Co] P1 lanes (field-reversed), the default.
  'dense' — w is [Fh, Fw, ceil(Cin/per), Co] bit-dense int32 words
            (per = 32 // w_bits); the kernel prologue expands words ->
            P1 lanes in VMEM, so HBM only ever holds w_bits per weight.

Layouts: input NHWC (C packed -> Cp lanes), output NHWC s32.  Padding is
applied by the wrapper ('VALID' inside the kernel).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import PackSpec
from repro.kernels import plan as plan_lib
from repro.kernels.ulppack_matmul import packed_dot


def expand_dense_taps(words: jax.Array, spec: PackSpec,
                      cin: int) -> jax.Array:
    """Bit-dense conv words [Fh, Fw, ceil(cin/per), Co] -> P1 lanes.

    The inverse of ops.dense_store_conv_weights followed by P1 packing, as
    pure shift/mask/reshape VPU ops so it can run inside a kernel prologue.
    Returns [Fh, Fw, cp, Co] lanes with cp = ceil(cin / n_pack).
    """
    per = 32 // spec.w_bits
    mask = (1 << spec.w_bits) - 1
    fh, fw, cwords, co = words.shape
    parts = [(words >> (spec.w_bits * j)) & mask for j in range(per)]
    lat = jnp.stack(parts, axis=3).reshape(fh, fw, cwords * per, co)
    cp = -(-cin // spec.n_pack)
    # dense_store pads cin -> cwords*per with zero lattice values, and
    # cwords*per >= cp*n_pack always (per >= n_pack), so this slice is the
    # zero-padded lattice pack_weights would have produced.
    lat = lat[:, :, :cp * spec.n_pack, :].reshape(fh, fw, cp, spec.n_pack, co)
    lanes = jnp.zeros((fh, fw, cp, co), jnp.int32)
    for j in range(spec.n_pack):
        lanes = lanes + (lat[:, :, :, j, :]
                         << (spec.shift * (spec.n_pack - 1 - j)))
    return lanes.astype(spec.lane_dtype)


def _kernel(x_ref, w_ref, o_ref, *scratch, spec: PackSpec, fh: int, fw: int,
            block_h: int, out_w: int, weight_store: str, k_full: int | None):
    cp = x_ref.shape[-1]
    bco = w_ref.shape[-1]
    if weight_store == "dense":
        # the co-block is the OUTERMOST grid dim, so the expanded lanes in
        # scratch stay valid across the whole (N, h-tile) inner sweep —
        # words are widened once per weight block, not once per grid step
        lanes_ref, = scratch
        @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
        def _expand():
            lanes_ref[...] = expand_dense_taps(w_ref[...], spec, k_full)
        wt = lanes_ref[...]
    else:
        wt = w_ref[...]
    acc = jnp.zeros((block_h * out_w, bco), jnp.int32)
    x = x_ref[0]                                   # [block_h+fh-1, W, Cp]
    for ih in range(fh):
        for iw in range(fw):
            window = jax.lax.slice(
                x, (ih, iw, 0), (ih + block_h, iw + out_w, cp))
            rows = window.reshape(block_h * out_w, cp)
            acc = acc + packed_dot(rows, wt[ih, iw], spec)
    o_ref[...] = acc.reshape(1, block_h, out_w, bco)


def _int_kernel(x_ref, w_ref, o_ref, *, fh: int, fw: int, block_h: int,
                out_w: int):
    cin = x_ref.shape[-1]
    bco = w_ref.shape[-1]
    acc = jnp.zeros((block_h * out_w, bco), jnp.int32)
    x = x_ref[0]
    for ih in range(fh):
        for iw in range(fw):
            window = jax.lax.slice(
                x, (ih, iw, 0), (ih + block_h, iw + out_w, cin))
            rows = window.reshape(block_h * out_w, cin)
            acc = acc + jax.lax.dot_general(
                rows, w_ref[ih, iw], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
    o_ref[...] = acc.reshape(1, block_h, out_w, bco)


def _maybe_pad_spatial(q_x, fh, fw, padding):
    if padding == "VALID":
        return q_x
    if padding == "SAME":
        ph, pw = fh - 1, fw - 1
        return jnp.pad(q_x, ((0, 0), (ph // 2, ph - ph // 2),
                             (pw // 2, pw - pw // 2), (0, 0)))
    raise ValueError(padding)


def _tiled_conv_call(kernel, x, w, *, fh, fw, block_h, block_co, out_h,
                     out_w, interpret, scratch_shapes=()):
    """Shared spatially-tiled pallas_call: halo-overlapped input h-tiles.

    ``block_h`` must already be resolved (the wrappers clamp it once and pass
    the same value here and into the kernel closure).  Grid order is
    (Co-block, N, h-tile): the weight block is outermost so per-block kernel
    prologue work (dense expansion scratch) amortizes over the inner sweep."""
    n, h, wd, cdim = x.shape
    assert 1 <= block_h <= out_h, (block_h, out_h)
    n_bh = -(-out_h // block_h)
    co = w.shape[-1]
    rem = (-co) % block_co
    if rem:
        w = jnp.pad(w, ((0, 0),) * 3 + ((0, rem),))
    gco = w.shape[-1] // block_co
    # Bottom-pad rows so every halo'd tile slice [hb*bh, hb*bh + bh+fh-1) is
    # in-bounds (tail tiles compute rows that are sliced off below).
    need_h = n_bh * block_h + fh - 1
    if need_h > h:
        x = jnp.pad(x, ((0, 0), (0, need_h - h), (0, 0), (0, 0)))

    out = pl.pallas_call(
        kernel,
        grid=(gco, n, n_bh),
        in_specs=[
            # element-indexed (Mosaic takes all dims as Elements or none):
            # tile hb starts at row hb*bh and reads fh-1 halo rows shared
            # with the next tile
            pl.BlockSpec((pl.Element(1), pl.Element(block_h + fh - 1),
                          pl.Element(wd), pl.Element(cdim)),
                         lambda j, i, hb, bh=block_h: (i, hb * bh, 0, 0)),
            pl.BlockSpec((fh, fw, w.shape[2], block_co),
                         lambda j, i, hb: (0, 0, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_h, out_w, block_co),
                               lambda j, i, hb: (i, hb, 0, j)),
        out_shape=jax.ShapeDtypeStruct(
            (n, n_bh * block_h, out_w, w.shape[-1]), jnp.int32),
        scratch_shapes=list(scratch_shapes),
        interpret=interpret, name="ulppack_conv2d",
    )(x, w)
    return out[:, :out_h, :, :co]


@functools.partial(
    jax.jit, static_argnames=("spec", "block_h", "block_co", "padding",
                              "interpret", "weight_store", "k_full"))
def ulppack_conv2d(x_packed: jax.Array, w_packed: jax.Array, spec: PackSpec,
                   *, block_h: int | None = None, block_co: int = 8,
                   padding: str = "VALID", interpret: bool | None = None,
                   weight_store: str = "lanes",
                   k_full: int | None = None) -> jax.Array:
    """Packed conv2d: [N,H,W,Cp] x [Fh,Fw,Cp,Co] -> s32 [N,Ho,Wo,Co].

    ``block_h=None`` keeps the whole output height in one tile (the legacy
    full-slab schedule); planners pass a VMEM-budgeted value.  With
    ``weight_store='dense'`` the weight operand is bit-dense int32 words
    [Fh, Fw, ceil(k_full/per), Co] and ``k_full`` (= Cin) is required.
    """
    if interpret is None:
        interpret = plan_lib.default_interpret()
    if not spec.feasible:
        raise ValueError(f"{spec} outside the overflow-free region")
    _, _, _, cp = x_packed.shape
    fh, fw, cdim, _ = w_packed.shape
    if weight_store == "lanes":
        assert cp == cdim, (cp, cdim)
    elif weight_store == "dense":
        if k_full is None:
            raise ValueError("weight_store='dense' requires k_full (Cin)")
        per = 32 // spec.w_bits
        assert cdim == -(-k_full // per), (cdim, k_full, per)
        assert cp == -(-k_full // spec.n_pack), (cp, k_full)
    else:
        raise ValueError(weight_store)
    x_packed = _maybe_pad_spatial(x_packed, fh, fw, padding)
    h, w = x_packed.shape[1], x_packed.shape[2]
    out_h, out_w = h - fh + 1, w - fw + 1
    bh = min(block_h or out_h, out_h)
    scratch = ()
    if weight_store == "dense":
        scratch = (pltpu.VMEM((fh, fw, cp, block_co), spec.lane_dtype),)
    return _tiled_conv_call(
        functools.partial(_kernel, spec=spec, fh=fh, fw=fw, block_h=bh,
                          out_w=out_w, weight_store=weight_store,
                          k_full=k_full),
        x_packed, w_packed, fh=fh, fw=fw, block_h=bh,
        block_co=block_co, out_h=out_h, out_w=out_w, interpret=interpret,
        scratch_shapes=scratch)


@functools.partial(
    jax.jit, static_argnames=("block_h", "block_co", "padding", "interpret"))
def int_conv2d(q_x: jax.Array, q_w: jax.Array, *, block_h: int | None = None,
               block_co: int = 8, padding: str = "VALID",
               interpret: bool | None = None) -> jax.Array:
    """Unpacked integer conv2d kernel (the paper's int16 baseline)."""
    if interpret is None:
        interpret = plan_lib.default_interpret()
    fh, fw, _, _ = q_w.shape
    q_x = _maybe_pad_spatial(q_x, fh, fw, padding)
    h, w = q_x.shape[1], q_x.shape[2]
    out_h, out_w = h - fh + 1, w - fw + 1
    bh = min(block_h or out_h, out_h)
    return _tiled_conv_call(
        functools.partial(_int_kernel, fh=fh, fw=fw, block_h=bh,
                          out_w=out_w),
        q_x, q_w, fh=fh, fw=fw, block_h=bh, block_co=block_co,
        out_h=out_h, out_w=out_w, interpret=interpret)
