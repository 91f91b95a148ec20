"""Quantizer properties: idempotence, STE gradients, LSQ, PACT, vmacsr ISA."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import hypothesis_or_stubs

from repro.core import quant, vmacsr

given, settings, st = hypothesis_or_stubs()


class TestAffine:
    @given(st.integers(2, 8))
    @settings(max_examples=10, deadline=None)
    def test_quantize_idempotent(self, bits):
        rng = np.random.default_rng(bits)
        x = jnp.asarray(rng.normal(size=(64,)), jnp.float32)
        scale, zp = quant.calibrate_minmax(x, bits)
        q = quant.quantize_affine(x, scale, zp, bits)
        dq = quant.dequantize_affine(q, scale, zp)
        q2 = quant.quantize_affine(dq, scale, zp, bits)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))

    def test_lattice_bounds(self):
        x = jnp.linspace(-10, 10, 101)
        scale, zp = quant.calibrate_minmax(x, 3)
        q = quant.quantize_affine(x, scale, zp, 3)
        assert int(q.min()) >= 0 and int(q.max()) <= 7

    def test_minmax_error_bound(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
        scale, zp = quant.calibrate_minmax(x, 8)
        dq = quant.dequantize_affine(
            quant.quantize_affine(x, scale, zp, 8), scale, zp)
        assert float(jnp.max(jnp.abs(dq - x))) <= float(scale) / 2 + 1e-6

    def test_sawb_positive(self):
        rng = np.random.default_rng(2)
        w = jnp.asarray(rng.normal(size=(128,)), jnp.float32)
        for bits in (2, 3, 4, 8):
            assert float(quant.sawb_scale(w, bits)) > 0

    @pytest.mark.parametrize("bits", [2, 3, 4, 8])
    def test_absmax_symmetric_extremes_roundtrip(self, bits):
        """+amax must land exactly on qmax (regression: the old scale
        targeted zp steps, sending +amax to 2^bits, which the clip in
        quantize_affine flattened by a full step) and -amax on 2*zp - qmax;
        both dequantize back to +/-amax exactly."""
        amax = 1.7
        x = jnp.asarray([-amax, -amax / 3, 0.0, amax / 2, amax], jnp.float32)
        scale, zp = quant.calibrate_absmax(x, bits, symmetric=True)
        qmax = (1 << bits) - 1
        q = quant.quantize_affine(x, scale, zp, bits)
        assert int(q[-1]) == qmax
        assert int(q[0]) == 2 * zp - qmax
        dq = np.asarray(quant.dequantize_affine(q, scale, zp))
        np.testing.assert_allclose(dq[-1], amax, rtol=1e-6)
        np.testing.assert_allclose(dq[0], -amax, rtol=1e-6)
        # interior points stay within half a step
        assert np.abs(dq - np.asarray(x)).max() <= float(scale) / 2 + 1e-6

    def test_absmax_symmetric_bits1_stays_finite(self):
        """bits=1 has qmax == zp; the qmax-zp denominator must clamp to 1
        (degenerate {-amax, 0} lattice) instead of producing scale=inf."""
        x = jnp.asarray([-2.0, 0.5, 2.0], jnp.float32)
        scale, zp = quant.calibrate_absmax(x, 1, symmetric=True)
        assert np.isfinite(float(scale)) and float(scale) == 2.0 and zp == 1
        q = quant.quantize_affine(x, scale, zp, 1)
        assert int(q.min()) >= 0 and int(q.max()) <= 1


class TestSTE:
    def test_fake_quant_grad_is_masked_identity(self):
        x = jnp.asarray([-5.0, -0.01, 0.0, 0.3, 0.7, 5.0])
        scale, zp = jnp.float32(0.1), jnp.float32(4.0)
        g = jax.grad(lambda v: jnp.sum(quant.fake_quant(v, scale, zp, 3)))(x)
        # range = [(0-4)*0.1, (7-4)*0.1] = [-0.4, 0.3]
        np.testing.assert_array_equal(np.asarray(g),
                                      np.asarray([0., 1., 1., 1., 0., 0.]))

    def test_lsq_step_gradient_sign(self):
        """Values clipped above push the step UP (to widen the range)."""
        x = jnp.full((16,), 10.0)
        step = jnp.float32(0.1)
        dstep = jax.grad(
            lambda s: jnp.sum(quant.lsq_fake_quant(x, s, 4, False)), 0)(step)
        assert float(dstep) > 0

    def test_lsq_forward_matches_fake_quant_midpoint(self):
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.normal(size=(32,)), jnp.float32)
        step = jnp.float32(0.2)
        y = quant.lsq_fake_quant(x, step, 4, True)
        want = quant.fake_quant(x, step, jnp.float32(8.0), 4)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-6)

    def test_pact_clip_grads(self):
        x = jnp.asarray([-1.0, 0.5, 2.0])
        alpha = jnp.float32(1.0)
        gx = jax.grad(lambda v: jnp.sum(quant.pact_clip(v, alpha, 4)))(x)
        ga = jax.grad(lambda a: jnp.sum(quant.pact_clip(x, a, 4)))(alpha)
        np.testing.assert_array_equal(np.asarray(gx), [0., 1., 0.])
        assert float(ga) == 1.0


class TestVmacsrISA:
    def test_vmacsr_semantics(self):
        vd = jnp.zeros((4,), jnp.int16)
        vs1 = jnp.asarray([17, 34, 51, 100], jnp.int16)   # packed lanes
        vs2 = jnp.asarray([16, 16, 16, 16], jnp.int16)
        out = vmacsr.vmacsr(vd, vs1, vs2, 4)
        np.testing.assert_array_equal(np.asarray(out), [17, 34, 51, 100])

    def test_vmacsr_kills_low_crossterm(self):
        """Per-product shift removes L before accumulation (paper Fig. 2)."""
        spec_shift = 8
        a_packed = jnp.asarray([3 + (2 << 8)], jnp.int32)    # a0=3, a1=2
        w_packed = jnp.asarray([1 + (2 << 8)], jnp.int32)    # w1=1, w0=2
        vd = jnp.zeros((1,), jnp.int32)
        for _ in range(100):   # way beyond the native k_tile for W2A2
            vd = vmacsr.vmacsr(vd, a_packed, w_packed, spec_shift)
        d = int(vd[0]) & 0xFF
        assert d == (100 * (3 * 2 + 2 * 1)) % 256

    def test_instruction_count_model(self):
        native = vmacsr.native_ulppack_instruction_count(256, k_tile=2)
        fused = vmacsr.vmacsr_instruction_count(256, k_tile=2)
        base = vmacsr.int16_instruction_count(256)
        assert fused.total < native.total < base.total * 2
        assert fused.shifts == 0 and native.shifts > 0


@pytest.mark.parametrize("n", [1, 3, 64, 100, 2048])
def test_ordered_sum_matches_sum_and_ignores_batch_shape(n):
    """core/reduce.ordered_sum is a sum (to f32 rounding), and a row's
    result is bit-identical whether it is reduced among 8 rows or 200."""
    from repro.core.reduce import ordered_sum

    x = np.random.default_rng(n).normal(size=(200, n)).astype(np.float32)
    got = np.asarray(jax.jit(ordered_sum)(jnp.asarray(x)))
    np.testing.assert_allclose(got, x.sum(-1, dtype=np.float64),
                               rtol=1e-5, atol=1e-4)
    few = np.asarray(jax.jit(ordered_sum)(jnp.asarray(x[:8])))
    assert np.array_equal(few, got[:8])
    kept = ordered_sum(jnp.asarray(x.T), axis=0, keepdims=True)
    assert kept.shape == (1, 200)
    np.testing.assert_array_equal(np.asarray(kept)[0], got)
