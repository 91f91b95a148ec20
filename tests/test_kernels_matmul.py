"""Pallas ulppack_matmul / int_matmul / quantize_pack vs ref.py oracles.

Kernels run with interpret=True (CPU container; TPU is the lowering target).
Integer paths must match EXACTLY.
"""

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import hypothesis_or_stubs

from repro.core.packing import PackSpec
from repro.kernels import ops, ref
from repro.kernels.ulppack_matmul import int_matmul, ulppack_matmul
from repro.core import packing

given, settings, st = hypothesis_or_stubs()


def lattice(rng, shape, bits):
    return jnp.asarray(rng.integers(0, 2**bits, size=shape), jnp.int32)


SPECS = [
    PackSpec(1, 1, jnp.int8.dtype),
    PackSpec(2, 1, jnp.int8.dtype),
    PackSpec(1, 1, jnp.int16.dtype),
    PackSpec(2, 2, jnp.int16.dtype),
    PackSpec(3, 2, jnp.int16.dtype),
    PackSpec(3, 3, jnp.int16.dtype),
    PackSpec(4, 3, jnp.int16.dtype),
    PackSpec(1, 1, jnp.int16.dtype, n_pack=4),
    # 8-bit fields exceed int8: the kernel recentres them (one side only)
    PackSpec(8, 1, jnp.int32.dtype, 2, 16),
    PackSpec(1, 8, jnp.int32.dtype, 2, 16),
]


class TestUlppackMatmulKernel:
    @pytest.mark.parametrize("spec", SPECS, ids=str)
    def test_exact_small(self, spec):
        rng = np.random.default_rng(1)
        m, k, n = 17, 130, 9
        q_a, q_w = lattice(rng, (m, k), spec.a_bits), lattice(rng, (k, n),
                                                              spec.w_bits)
        ap = packing.pack_activations(q_a, spec, -1)
        wp = packing.pack_weights(q_w, spec, 0)
        got = ulppack_matmul(ap, wp, spec, block_m=8, block_n=8, chunks=2,
                             interpret=True)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(ref.matmul_i32_ref(q_a, q_w)))

    @given(st.integers(1, 40), st.integers(1, 200), st.integers(1, 24),
           st.sampled_from([(1, 1), (2, 2), (3, 3)]))
    @settings(max_examples=8, deadline=None)
    def test_shape_sweep(self, m, k, n, wa):
        spec = PackSpec(wa[0], wa[1], jnp.int16.dtype)
        rng = np.random.default_rng(m * 1000 + k * 10 + n)
        q_a, q_w = lattice(rng, (m, k), spec.a_bits), lattice(rng, (k, n),
                                                              spec.w_bits)
        ap = packing.pack_activations(q_a, spec, -1)
        wp = packing.pack_weights(q_w, spec, 0)
        got = ulppack_matmul(ap, wp, spec, block_m=16, block_n=16, chunks=3,
                             interpret=True)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(ref.matmul_i32_ref(q_a, q_w)))

    def test_worst_case_lattice_at_tile_bound(self):
        spec = PackSpec(3, 3, jnp.int16.dtype)   # k_tile = 2 (tight)
        k = 64
        q_a = jnp.full((4, k), spec.max_a, jnp.int32)
        q_w = jnp.full((k, 4), spec.max_w, jnp.int32)
        ap = packing.pack_activations(q_a, spec, -1)
        wp = packing.pack_weights(q_w, spec, 0)
        got = ulppack_matmul(ap, wp, spec, block_m=8, block_n=8, chunks=4,
                             interpret=True)
        assert int(got[0, 0]) == k * spec.max_a * spec.max_w


class TestIntMatmulKernel:
    @pytest.mark.parametrize("bits", [8, 16])
    def test_exact(self, bits):
        rng = np.random.default_rng(9)
        q_a = jnp.asarray(rng.integers(-100, 100, (33, 257)), jnp.int32)
        q_w = jnp.asarray(rng.integers(-100, 100, (257, 19)), jnp.int32)
        dt = jnp.int8 if bits == 8 else jnp.int16
        q_a8 = jnp.clip(q_a, -127, 127).astype(dt)
        q_w8 = jnp.clip(q_w, -127, 127).astype(dt)
        got = int_matmul(q_a8, q_w8, block_m=16, block_n=16, block_k=64,
                         interpret=True)
        want = ref.matmul_i32_ref(q_a8.astype(jnp.int32),
                                  q_w8.astype(jnp.int32))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestQuantizePackKernel:
    @pytest.mark.parametrize("spec", [PackSpec(2, 2, jnp.int16.dtype),
                                      PackSpec(1, 1, jnp.int8.dtype),
                                      PackSpec(1, 8, jnp.int32.dtype, 2, 16),
                                      PackSpec(2, 2, jnp.int32.dtype, 4, 8)],
                             ids=str)
    def test_matches_ref(self, spec):
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.normal(size=(37, 129)), jnp.float32)
        scale = jnp.float32(0.1)
        zp = jnp.int32(1 << (spec.a_bits - 1))
        from repro.kernels.quant_pack import quantize_pack
        packed, rs = quantize_pack(x, scale, zp, spec, block_m=16,
                                   block_k=32, interpret=True)
        want_p, want_rs = ref.quantize_pack_ref(x, scale, zp, spec)
        np.testing.assert_array_equal(np.asarray(packed), np.asarray(want_p))
        np.testing.assert_array_equal(np.asarray(rs[:, 0]),
                                      np.asarray(want_rs))


class TestQuantizedLinearEndToEnd:
    def test_matches_float_oracle(self):
        spec = PackSpec(3, 3, jnp.int16.dtype)
        rng = np.random.default_rng(11)
        x = jnp.asarray(rng.normal(size=(5, 96)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(96, 7)) * 0.2, jnp.float32)
        a_scale, a_zp = jnp.float32(0.05), jnp.int32(4)
        w_scale, w_zp = jnp.float32(0.01), jnp.int32(4)
        wp, col_sums = ops.prepare_weights(w, w_scale, w_zp, spec)
        got = ops.quantized_linear(x, wp, col_sums, a_scale, a_zp, w_scale,
                                   w_zp, spec, backend="xla")
        want = ref.quantized_linear_ref(x, w, a_scale, a_zp, w_scale, w_zp,
                                        spec.a_bits, spec.w_bits)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_pallas_and_xla_backends_agree(self):
        spec = PackSpec(2, 2, jnp.int16.dtype)
        rng = np.random.default_rng(13)
        x = jnp.asarray(rng.normal(size=(3, 64)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(64, 9)) * 0.3, jnp.float32)
        wp, cs = ops.prepare_weights(w, jnp.float32(0.02), jnp.int32(2), spec)
        a = ops.quantized_linear(x, wp, cs, jnp.float32(0.07), jnp.int32(1),
                                 jnp.float32(0.02), jnp.int32(2), spec,
                                 backend="pallas")
        b = ops.quantized_linear(x, wp, cs, jnp.float32(0.07), jnp.int32(1),
                                 jnp.float32(0.02), jnp.int32(2), spec,
                                 backend="xla")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
