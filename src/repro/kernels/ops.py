"""Public kernel API: plan-dispatched packed ops + affine-corrected linear.

Every entry point routes through a ``KernelPlan`` (kernels/plan.py): callers
either pass a prebuilt per-layer plan (the deployed path — serve/prepare.py
and models/cnn.py build plans once at preparation time) or a plan is looked
up from the memoized planners on first use for a shape signature.  The
'pallas' and 'xla' implementations of each op are entries in the plan
module's backend registry — there is no ad-hoc backend resolution here.

``backend``:
  'pallas'  — the fused TPU kernels (interpret=True on CPU): the Sparq path.
  'xla'     — pure-XLA packed math (packing.packed_matmul_reference): the
              "native ULPPACK on stock hardware" path, also used inside jitted
              multi-device step functions where a python-gridded interpret
              kernel would be prohibitively slow on CPU.
  'auto'    — pallas on TPU, xla elsewhere (resolved by the planner).

Both backends are bit-exact against kernels/ref.py oracles; tests enforce it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing
from repro.core.packing import PackSpec
from repro.kernels import plan as plan_lib
from repro.kernels import quant_pack as _quant_pack
from repro.kernels import ulppack_conv2d as _conv
from repro.kernels import ulppack_matmul as _matmul
from repro.kernels.plan import KernelPlan


# ---------------------------------------------------------------------------
# packed_matmul
# ---------------------------------------------------------------------------

def packed_matmul(a_packed, w_packed, spec: PackSpec, *,
                  backend: str = "auto", weight_store: str = "lanes",
                  k_full: int | None = None,
                  plan: KernelPlan | None = None) -> jax.Array:
    """[.., Kp] x [Kp, N] -> exact s32 dot of the underlying lattices.

    With ``weight_store='dense'`` (or a dense plan) ``w_packed`` is bit-dense
    int32 words [ceil(k_full/per), N] and ``k_full`` is the unpacked K.
    """
    lead = a_packed.shape[:-1]
    a2 = a_packed.reshape(-1, a_packed.shape[-1])
    if plan is None:
        plan = plan_lib.plan_packed_matmul(
            a2.shape[0], a2.shape[1], w_packed.shape[-1], spec,
            backend=backend, weight_store=weight_store, k_full=k_full)
    out = plan_lib.dispatch_on_mesh(
        plan, (a2, w_packed), functools.partial(_column_layout, plan, a2,
                                                w_packed))
    return out.reshape(*lead, w_packed.shape[-1])


def _rows_layout(mesh, x2):
    """Activation rows [M, K] on the serving mesh: split over its data
    axes where they divide M, as ``sharding.constrain(x, 'dp', ...)``
    lays them out, else whole on every device.  Returns (spec, rows one
    device holds)."""
    from repro.parallel.sharding import _axis_size, spec_on_mesh
    spec = spec_on_mesh(mesh, x2.shape, "dp", None)
    return spec, x2.shape[0] // _axis_size(mesh, spec[0])


def _column_layout(plan: KernelPlan, a2, w, mesh):
    """Per-device layout of the packed matmul (plan.dispatch_on_mesh): the
    activation rows against the weight columns as the serving ShardPlan
    stores them — split over 'model', or whole on every device where
    'model' does not divide N — so each device reads only the weights it
    holds, and the output keeps both layouts."""
    from jax.sharding import PartitionSpec as P

    from repro.parallel.sharding import _axis_size, spec_on_mesh
    rows, m = _rows_layout(mesh, a2)
    cols = spec_on_mesh(mesh, w.shape, None, "model")
    n = w.shape[-1] // _axis_size(mesh, cols[1])
    local = plan if (m, n) == (a2.shape[0], w.shape[-1]) else \
        plan_lib.plan_packed_matmul(
            m, a2.shape[1], n, plan.spec, backend=plan.backend,
            weight_store=plan.weight_store, k_full=plan.k_full)
    return (rows, cols), P(rows[0], cols[1]), local


def _dense_to_lanes(words, spec: PackSpec, k_full: int):
    """Expand bit-dense weight words [Kw, N] -> P1 lanes [Kp, N]."""
    q_w = dense_load_weights(words, spec.w_bits, k_full)
    return packing.pack_weights(q_w, spec, axis=0)


@plan_lib.register_backend("packed_matmul", "pallas")
def _packed_matmul_pallas(plan: KernelPlan, a2, w):
    if plan.weight_store == "dense":
        # Matmul keeps dense expansion at trace level (the HBM weight operand
        # is still the dense words); the conv kernel does it in its prologue.
        w = _dense_to_lanes(w, plan.spec, plan.k_full)
    return _matmul.ulppack_matmul(
        a2, w, plan.spec, block_m=plan.block_m, block_n=plan.block_n,
        chunks=plan.chunks, interpret=plan.interpret)


@plan_lib.register_backend("packed_matmul", "xla")
def _packed_matmul_xla(plan: KernelPlan, a2, w):
    if plan.weight_store == "dense":
        w = _dense_to_lanes(w, plan.spec, plan.k_full)
    return _xla_packed_matmul(a2, w, plan.spec)


def _xla_packed_matmul(a_packed, w_packed, spec: PackSpec,
                       batched_rows: int = 1024):
    """Packed matmul on pre-packed lanes at the XLA level (tiled extraction).

    Two formulations, chosen by row count:
      * rows <= batched_rows (decode/serve): ONE batched dot_general over all
        k-tiles + extraction + tile-sum.  Scan-free, so compiled FLOP counts
        are exact for the roofline analysis (XLA cost analysis does not
        multiply while-loop bodies by trip count).
      * large rows (training-scale fallback): lax.scan over k-tiles — same
        math as packing.packed_matmul_reference.
    """
    kt = spec.k_tile
    a = packing.pad_to_multiple(a_packed, -1, kt)
    w = packing.pad_to_multiple(w_packed, 0, kt)
    n_tiles = a.shape[-1] // kt
    rows = int(np.prod(a_packed.shape[:-1])) if a_packed.ndim > 1 else 1

    if rows <= batched_rows:
        a3 = a.reshape(*a.shape[:-1], n_tiles, kt)        # [.., nc, kt]
        w3 = w.reshape(n_tiles, kt, w.shape[-1])          # [nc, kt, N]
        nd = a3.ndim
        tot = jax.lax.dot_general(
            a3, w3, (((nd - 1,), (1,)), ((nd - 2,), (0,))),
            preferred_element_type=jnp.int32)             # [nc, .., N]
        return jnp.sum(packing.extract_dot(tot, spec), axis=0)

    a_t = jnp.moveaxis(a.reshape(*a.shape[:-1], n_tiles, kt), -2, 0)
    w_t = w.reshape(n_tiles, kt, w.shape[-1])

    def body(carry, xs):
        a_c, w_c = xs
        tot = jax.lax.dot_general(a_c, w_c, (((a_c.ndim - 1,), (0,)),
                                             ((), ())),
                                  preferred_element_type=jnp.int32)
        return carry + packing.extract_dot(tot, spec), None

    init = jnp.zeros((*a_packed.shape[:-1], w_packed.shape[-1]), jnp.int32)
    out, _ = jax.lax.scan(body, init, (a_t, w_t))
    return out


# ---------------------------------------------------------------------------
# quantize_pack
# ---------------------------------------------------------------------------

def quantize_pack(x, scale, zero_point, spec: PackSpec, *,
                  backend: str = "auto", plan: KernelPlan | None = None):
    """Quantize + P1-pack activations along the last axis; also row sums."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if plan is None:
        plan = plan_lib.plan_quantize_pack(x2.shape[0], x2.shape[1], spec,
                                           backend=backend)
    packed, rs = plan_lib.dispatch_on_mesh(
        plan, (x2, jnp.asarray(scale), jnp.asarray(zero_point)),
        functools.partial(_pack_layout, plan, x2))
    kp = packed.shape[-1]
    return packed.reshape(*lead, kp), rs.reshape(*lead, 1)


def _pack_layout(plan: KernelPlan, x2, mesh):
    """Per-device layout of quantize_pack: each device packs the
    activation rows it holds (scalars replicate)."""
    from jax.sharding import PartitionSpec as P
    rows, m = _rows_layout(mesh, x2)
    local = plan if m == x2.shape[0] else plan_lib.plan_quantize_pack(
        m, x2.shape[1], plan.spec, backend=plan.backend)
    return (rows, P(), P()), (rows, rows), local


@plan_lib.register_backend("quantize_pack", "pallas")
def _quantize_pack_pallas(plan: KernelPlan, x2, scale, zero_point):
    return _quant_pack.quantize_pack(
        x2, scale, zero_point, plan.spec, block_m=plan.block_m,
        block_k=plan.block_k, interpret=plan.interpret)


@plan_lib.register_backend("quantize_pack", "xla")
def _quantize_pack_xla(plan: KernelPlan, x2, scale, zero_point):
    from repro.core import quant
    q = quant.quantize_affine(x2, scale, zero_point, plan.spec.a_bits)
    packed = packing.pack_activations(q, plan.spec, axis=-1)
    rs = jnp.sum(q, axis=-1, keepdims=True).astype(jnp.int32)
    return packed, rs


# ---------------------------------------------------------------------------
# packed_conv2d
# ---------------------------------------------------------------------------

def packed_conv2d(x_packed, w_packed, spec: PackSpec, *,
                  padding: str = "SAME", backend: str = "auto",
                  weight_store: str = "lanes", k_full: int | None = None,
                  plan: KernelPlan | None = None):
    """Packed conv2d [N,H,W,Cp] x [Fh,Fw,Cdim,Co] -> s32 NHWC.

    The spatial tiling (block_h) and weight-storage mode come from the plan;
    see kernels/plan.py.  With ``weight_store='dense'`` the weight operand is
    bit-dense words; pass ``k_full`` (= Cin) when it is not a multiple of
    n_pack (the planner's default rounds up, which the zero-padded words
    make equivalent).
    """
    if plan is None:
        plan = plan_lib.plan_packed_conv2d(
            tuple(x_packed.shape), tuple(w_packed.shape), spec,
            padding=padding, backend=backend, weight_store=weight_store,
            k_full=k_full)
    return plan_lib.dispatch(plan, x_packed, w_packed, padding)


@plan_lib.register_backend("packed_conv2d", "pallas")
def _packed_conv2d_pallas(plan: KernelPlan, x_packed, w_packed, padding):
    return _conv.ulppack_conv2d(
        x_packed, w_packed, plan.spec, block_h=plan.block_h,
        block_co=plan.block_co, padding=padding, interpret=plan.interpret,
        weight_store=plan.weight_store, k_full=plan.k_full)


@plan_lib.register_backend("packed_conv2d", "xla")
def _packed_conv2d_xla(plan: KernelPlan, x_packed, w_packed, padding):
    spec = plan.spec
    if plan.weight_store == "dense":
        w_packed = _conv.expand_dense_taps(w_packed, spec, plan.k_full)
    kt = spec.k_tile
    cp = x_packed.shape[-1]
    out = None
    for c0 in range(0, cp, kt):
        c1 = min(c0 + kt, cp)
        tot = jax.lax.conv_general_dilated(
            x_packed[..., c0:c1].astype(jnp.int32),
            w_packed[:, :, c0:c1, :].astype(jnp.int32),
            (1, 1), padding, dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.int32)
        d = packing.extract_dot(tot, spec)
        out = d if out is None else out + d
    return out


# ---------------------------------------------------------------------------
# int_matmul
# ---------------------------------------------------------------------------

def int_matmul(q_a, q_w, *, backend: str = "auto",
               plan: KernelPlan | None = None):
    lead = q_a.shape[:-1]
    a2 = q_a.reshape(-1, q_a.shape[-1])
    if plan is None:
        plan = plan_lib.plan_int_matmul(a2.shape[0], a2.shape[1],
                                        q_w.shape[-1], backend=backend)
    out = plan_lib.dispatch(plan, a2, q_w)
    return out.reshape(*lead, q_w.shape[-1])


@plan_lib.register_backend("int_matmul", "pallas")
def _int_matmul_pallas(plan: KernelPlan, a2, q_w):
    return _matmul.int_matmul(a2, q_w, block_m=plan.block_m,
                              block_n=plan.block_n, block_k=plan.block_k,
                              interpret=plan.interpret)


@plan_lib.register_backend("int_matmul", "xla")
def _int_matmul_xla(plan: KernelPlan, a2, q_w):
    return jax.lax.dot_general(a2.astype(jnp.int32), q_w.astype(jnp.int32),
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


# ---------------------------------------------------------------------------
# quantized_linear (the deployed Sparq linear)
# ---------------------------------------------------------------------------

def quantized_linear(x, w_packed, w_col_sums, a_scale, a_zp, w_scale, w_zp,
                     spec: PackSpec, *, bias=None, backend: str = "auto",
                     weight_store: str = "lanes",
                     plan: KernelPlan | None = None, out_dtype=jnp.float32):
    """The deployed Sparq linear: runtime pack + packed matmul + dequant.

    x:          [..., K] float activations
    w_packed:   [Kp, N] offline-packed weight lanes (field-reversed), or
                [Kw, N] bit-dense int32 words under weight_store='dense'
    w_col_sums: [N] s32 offline per-column lattice sums
    Returns float [..., N]  ==  quantized_linear_ref to float tolerance.
    """
    k = x.shape[-1]
    if plan is None:
        rows = int(np.prod(x.shape[:-1])) if x.ndim > 1 else 1
        kp = -(-k // spec.n_pack)
        plan = plan_lib.plan_packed_matmul(
            rows, kp, w_packed.shape[-1], spec, backend=backend,
            weight_store=weight_store,
            k_full=k if weight_store == "dense" else None)
    a_packed, a_sums = quantize_pack(x, a_scale, a_zp, spec,
                                     backend=plan.backend)
    acc = packed_matmul(a_packed, w_packed, spec, plan=plan)
    acc = acc.astype(jnp.float32)
    corr = (acc
            - jnp.asarray(w_zp, jnp.float32) * a_sums.astype(jnp.float32)
            - jnp.asarray(a_zp, jnp.float32)
            * w_col_sums.astype(jnp.float32)[None, :]
            .reshape((1,) * (acc.ndim - 1) + (-1,))
            + (k * jnp.asarray(a_zp, jnp.float32)
               * jnp.asarray(w_zp, jnp.float32)))
    out = (jnp.asarray(a_scale, jnp.float32)
           * jnp.asarray(w_scale, jnp.float32) * corr)
    if bias is not None:
        out = out + bias
    return out.astype(out_dtype)


# ---------------------------------------------------------------------------
# Offline weight preparation
# ---------------------------------------------------------------------------

def prepare_weights(w, w_scale, w_zp, spec: PackSpec, *,
                    weight_store: str = "lanes"):
    """Offline weight path: quantize, pack (field-reversed), column sums.

    ``weight_store='dense'`` stores the lattice bit-dense (int32 words,
    true w_bits/value HBM footprint) instead of as P1 lanes.
    """
    from repro.core import quant
    q_w = quant.quantize_affine(w, w_scale, w_zp, spec.w_bits)
    col_sums = jnp.sum(q_w, axis=0).astype(jnp.int32)
    if weight_store == "dense":
        return dense_store_weights(q_w, spec.w_bits), col_sums
    return packing.pack_weights(q_w, spec, axis=0), col_sums


# ---------------------------------------------------------------------------
# Dense sub-byte weight storage (beyond-paper, §Perf memory-term
# optimization): store w_bits-wide lattice values bit-dense in int32 words
# (true w_bits/value HBM footprint) and expand to P1 lanes at use.  On TPU
# the conv2d expansion lives in the Pallas kernel's VMEM prologue
# (ulppack_conv2d.expand_dense_taps); the matmul / XLA paths materialize the
# lanes at trace level (still saving HBM reads of the weight tensor).
# ---------------------------------------------------------------------------

def dense_store_weights(q_w: jax.Array, w_bits: int) -> jax.Array:
    """[K, N] lattice (< 2^w_bits) -> [ceil(K/per), N] int32 bit-dense."""
    return packing.pack_words(q_w, w_bits, axis=0)


def dense_load_weights(words: jax.Array, w_bits: int, k: int) -> jax.Array:
    """Inverse of dense_store_weights -> [K, N] int32 lattice."""
    return packing.unpack_words(words, w_bits, k, axis=0)


def dense_store_conv_weights(q_w: jax.Array, w_bits: int) -> jax.Array:
    """[Fh, Fw, Cin, Co] lattice -> [Fh, Fw, ceil(Cin/per), Co] int32 words.

    Word-packs the input-channel axis independently per (fh, fw, co) tap, the
    layout ulppack_conv2d's dense prologue expands.
    """
    fh, fw, cin, co = q_w.shape
    flat = q_w.transpose(2, 0, 1, 3).reshape(cin, fh * fw * co)
    words = dense_store_weights(flat, w_bits)
    return words.reshape(-1, fh, fw, co).transpose(1, 2, 0, 3)
