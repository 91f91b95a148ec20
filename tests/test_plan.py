"""KernelPlan planner + backend registry + dense weight storage."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.packing import PackSpec
from repro.kernels import ops, ref
from repro.kernels import plan as plan_lib


SPEC = PackSpec(2, 2, jnp.int16.dtype)


class TestPlanner:
    def test_plan_is_memoized_per_signature(self):
        a = plan_lib.plan_packed_matmul(8, 32, 64, SPEC, backend="xla")
        b = plan_lib.plan_packed_matmul(8, 32, 64, SPEC, backend="xla")
        assert a is b
        c = plan_lib.plan_packed_matmul(9, 32, 64, SPEC, backend="xla")
        assert c is not a

    def test_plan_is_hashable_and_frozen(self):
        p = plan_lib.plan_packed_matmul(8, 32, 64, SPEC, backend="xla")
        hash(p)
        with pytest.raises(Exception):
            p.backend = "pallas"

    def test_resolve_backend(self):
        assert plan_lib.resolve_backend("pallas") == "pallas"
        assert plan_lib.resolve_backend("auto") in ("pallas", "xla")
        with pytest.raises(ValueError):
            plan_lib.resolve_backend("cuda")

    def test_unresolved_backend_rejected_by_plan(self):
        with pytest.raises(ValueError):
            plan_lib.KernelPlan(op="packed_matmul", backend="auto")

    def test_dense_plan_requires_k_full(self):
        with pytest.raises(ValueError):
            plan_lib.KernelPlan(op="packed_matmul", backend="xla",
                                spec=SPEC, weight_store="dense")

    def test_conv_block_h_shrinks_with_budget(self):
        x_shape, w_shape = (1, 256, 256, 16), (7, 7, 16, 32)
        big = plan_lib.plan_packed_conv2d(x_shape, w_shape, SPEC,
                                          padding="VALID", backend="xla")
        small = plan_lib.plan_packed_conv2d(x_shape, w_shape, SPEC,
                                            padding="VALID", backend="xla",
                                            vmem_budget=256 * 1024)
        assert small.block_h < big.block_h
        assert small.vmem_bytes <= 256 * 1024
        tiny = plan_lib.plan_packed_conv2d(x_shape, w_shape, SPEC,
                                           padding="VALID", backend="xla",
                                           vmem_budget=64 * 1024)
        assert tiny.block_h <= small.block_h

    def test_conv_block_h_capped_at_out_h(self):
        p = plan_lib.plan_packed_conv2d((1, 10, 10, 4), (3, 3, 4, 8), SPEC,
                                        padding="VALID", backend="xla")
        assert p.block_h <= 8   # out_h = 10 - 3 + 1

    def test_interpret_defaults_from_device(self):
        """Regression: hand-built plans and direct kernel calls must default
        ``interpret`` from the device (interpreter only off-TPU), not a
        hard-coded True that would silently interpret on TPU."""
        import inspect

        import jax

        from repro.kernels import quant_pack, ulppack_conv2d, ulppack_matmul

        want = jax.default_backend() != "tpu"
        assert plan_lib.default_interpret() == want
        hand_built = plan_lib.KernelPlan(op="int_matmul", backend="xla")
        assert hand_built.interpret == want
        planned = plan_lib.plan_int_matmul(8, 32, 16, backend="xla")
        assert planned.interpret == hand_built.interpret == want
        for fn in (quant_pack.quantize_pack, ulppack_matmul.ulppack_matmul,
                   ulppack_matmul.int_matmul, ulppack_conv2d.ulppack_conv2d,
                   ulppack_conv2d.int_conv2d):
            sig = inspect.signature(fn)
            assert sig.parameters["interpret"].default is None, fn

    @pytest.mark.parametrize("m,kp,n", [(1, 32, 64), (8, 1024, 2048),
                                        (256, 2816, 2048), (13, 100, 7),
                                        (3072, 1024, 5632)])
    def test_matmul_blocks_tpu_aligned(self, m, kp, n):
        """Every heuristic packed-matmul plan uses blocks the TPU compiler
        accepts (rows in 8s, N and K in 128-lane units), and the K block
        divides the 128-rounded packed K so weights are never re-padded."""
        p = plan_lib.plan_packed_matmul(m, kp, n, SPEC, backend="pallas")
        assert plan_lib.matmul_tiles_ok(p.block_m, p.block_n, p.chunks)
        assert p.block_m >= min(m, 128)
        assert (-(-kp // plan_lib.MATMUL_LANES)) % p.chunks == 0
        small = plan_lib.plan_packed_matmul(m, kp, n, SPEC, backend="pallas",
                                            vmem_budget=300 * 1024)
        assert plan_lib.matmul_tiles_ok(small.block_m, small.block_n,
                                        small.chunks)

    def test_describe_reports_tiles(self):
        p = plan_lib.plan_packed_conv2d((1, 64, 64, 16), (7, 7, 16, 32),
                                        SPEC, padding="SAME", backend="xla")
        d = p.describe()
        assert d["op"] == "packed_conv2d"
        assert d["block_h"] >= 1 and d["block_co"] >= 1
        assert 0 < d["vmem_frac"] < 1


class TestRegistry:
    def test_all_public_ops_registered_for_both_backends(self):
        ops_reg = plan_lib.registered_ops()
        for op in ("packed_matmul", "packed_conv2d", "quantize_pack",
                   "int_matmul"):
            assert (op, "pallas") in ops_reg, op
            assert (op, "xla") in ops_reg, op

    def test_unknown_backend_raises(self):
        with pytest.raises(KeyError, match="no backend"):
            plan_lib.get_backend("packed_matmul", "cuda")

    def test_ops_module_has_no_adhoc_resolution(self):
        import inspect

        src = inspect.getsource(ops)
        assert "_resolve" not in src
        assert "def _interpret" not in src

    def test_dispatch_routes_by_plan(self):
        rng = np.random.default_rng(0)
        from repro.core import packing
        q_a = jnp.asarray(rng.integers(0, 4, (5, 40)), jnp.int32)
        q_w = jnp.asarray(rng.integers(0, 4, (40, 7)), jnp.int32)
        ap = packing.pack_activations(q_a, SPEC, -1)
        wp = packing.pack_weights(q_w, SPEC, 0)
        want = ref.matmul_i32_ref(q_a, q_w)
        for backend in ("pallas", "xla"):
            got = ops.packed_matmul(ap, wp, SPEC, backend=backend)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestDenseStorage:
    @pytest.mark.parametrize("w_bits", [1, 2, 4])
    def test_roundtrip(self, w_bits):
        rng = np.random.default_rng(w_bits)
        for k, n in [(1, 1), (5, 3), (64, 16), (97, 8)]:
            q = jnp.asarray(rng.integers(0, 2 ** w_bits, (k, n)), jnp.int32)
            words = ops.dense_store_weights(q, w_bits)
            back = ops.dense_load_weights(words, w_bits, k)
            np.testing.assert_array_equal(np.asarray(back), np.asarray(q))

    @pytest.mark.parametrize("w_bits", [1, 2, 4])
    def test_footprint_is_bit_exact(self, w_bits):
        per = 32 // w_bits
        q = jnp.zeros((per * 8, 64), jnp.int32)
        words = ops.dense_store_weights(q, w_bits)
        assert words.size * 32 == q.size * w_bits

    @pytest.mark.parametrize("w_bits", [1, 2, 4])
    def test_conv_words_roundtrip_via_expand(self, w_bits):
        from repro.kernels.ulppack_conv2d import expand_dense_taps
        from repro.core import packing

        spec = PackSpec(w_bits, 1, jnp.int16.dtype)
        rng = np.random.default_rng(3 * w_bits)
        q_w = jnp.asarray(rng.integers(0, 2 ** w_bits, (3, 3, 10, 5)),
                          jnp.int32)
        words = ops.dense_store_conv_weights(q_w, w_bits)
        lanes = expand_dense_taps(words, spec, 10)
        want = packing.pack_weights(q_w, spec, axis=2)
        np.testing.assert_array_equal(np.asarray(lanes), np.asarray(want))

    def test_prepare_weights_dense_matches_lanes_linear(self):
        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.normal(size=(5, 48)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(48, 12)) * 0.05, jnp.float32)
        args = (jnp.float32(0.07), jnp.int32(1), jnp.float32(0.02),
                jnp.int32(2))
        wp, cs = ops.prepare_weights(w, jnp.float32(0.02), jnp.int32(2),
                                     SPEC)
        wd, cs2 = ops.prepare_weights(w, jnp.float32(0.02), jnp.int32(2),
                                      SPEC, weight_store="dense")
        np.testing.assert_array_equal(np.asarray(cs), np.asarray(cs2))
        a = ops.quantized_linear(x, wp, cs, *args, SPEC, backend="xla")
        b = ops.quantized_linear(x, wd, cs2, *args, SPEC, backend="xla",
                                 weight_store="dense")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


class TestServePlans:
    def test_engine_style_layer_plans(self):
        import jax
        from repro.models import common
        from repro.serve import prepare

        from repro.configs import get_config
        cfg = get_config("sparq-cnn")
        key = jax.random.PRNGKey(0)
        p = common.dense_init(key, 32, 16, quantized=True, qcfg=cfg.quant)
        tree = {"blocks": [{"mlp": p}], "head": {"kernel": jnp.zeros((4, 4))}}
        packed = prepare.prepare_serving_params(tree, cfg)
        plans = prepare.build_layer_plans(packed, cfg, batch_rows=4)
        assert list(plans) == ["blocks[0]/mlp"]
        plan = plans["blocks[0]/mlp"]
        assert plan.op == "packed_matmul"
        assert plan.weight_store == "lanes"
        # the memoized planner returns the same object at dispatch shape
        again = plan_lib.plan_packed_matmul(
            4, packed["blocks"][0]["mlp"]["w_packed"].shape[0], 16,
            PackSpec(cfg.quant.w_bits, cfg.quant.a_bits,
                     jnp.dtype(cfg.quant.lane_dtype), cfg.quant.n_pack),
            backend="auto", weight_store="lanes", k_full=None)
        assert again is plan

    def test_dense_layer_plans_use_exact_k(self):
        """With K not a word multiple, the offline dense plan must key the
        exact K (recorded at pack time), matching dispatch-time lookup."""
        import jax
        from repro.configs import get_config
        from repro.models import common
        from repro.serve import prepare

        cfg = get_config("sparq-cnn")
        k = 40                          # per = 16 for w_bits=2; 40 % 16 != 0
        key = jax.random.PRNGKey(1)
        p = common.dense_init(key, k, 16, quantized=True, qcfg=cfg.quant)
        tree = {"mlp": p}
        packed = prepare.prepare_serving_params(tree, cfg, dense_store=True)
        assert packed["mlp"]["k_full"] == k
        plans = prepare.build_layer_plans(packed, cfg, batch_rows=3)
        plan = plans["mlp"]
        assert plan.weight_store == "dense" and plan.k_full == k
        spec = PackSpec(cfg.quant.w_bits, cfg.quant.a_bits,
                        jnp.dtype(cfg.quant.lane_dtype), cfg.quant.n_pack)
        dispatch_plan = plan_lib.plan_packed_matmul(
            3, -(-k // spec.n_pack), 16, spec, backend="auto",
            weight_store="dense", k_full=k)
        assert dispatch_plan is plan
        # and the layer itself stays correct end-to-end
        x = jax.random.normal(key, (3, k))
        y_l = common.dense_apply(
            common.pack_dense_params(p, cfg.quant), x, qcfg=cfg.quant,
            quant_mode="packed", compute_dtype=jnp.float32)
        y_d = common.dense_apply(packed["mlp"], x, qcfg=cfg.quant,
                                 quant_mode="packed",
                                 compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(y_l), np.asarray(y_d),
                                   rtol=1e-5, atol=1e-5)
