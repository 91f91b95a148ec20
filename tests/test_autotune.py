"""KernelPlan autotuner: cache load/fallback, planner consultation,
determinism, and the committed CPU tuning cache (DESIGN.md §14)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.packing import PackSpec
from repro.kernels import autotune, ops, ref
from repro.kernels import plan as plan_lib

SPEC = PackSpec(2, 2, jnp.int16.dtype)


@pytest.fixture(autouse=True)
def _restore_active_cache():
    """Every test starts from the lazy default and leaves no cache behind."""
    autotune.reset_active_cache()
    yield
    autotune.reset_active_cache()


def _empty():
    return autotune.set_active_cache(autotune.TuningCache(device="cpu"))


class TestCacheFile:
    def test_save_load_roundtrip(self, tmp_path):
        c = autotune.TuningCache(device="cpu")
        key = autotune.matmul_key(8, 32, 64, SPEC, backend="xla")
        c.store(key, {"block_m": 32, "block_n": 128, "chunks": 2})
        path = c.save(str(tmp_path / "cache.json"))
        back = autotune.TuningCache.load(path)
        assert back is not None
        assert back.device == "cpu"
        assert back.lookup(key)["block_m"] == 32

    def test_missing_file_is_silent_none(self, tmp_path):
        assert autotune.TuningCache.load(str(tmp_path / "nope.json")) is None

    def test_corrupt_file_warns_and_falls_back(self, tmp_path):
        p = tmp_path / "bad.json"
        p.write_text("{not json")
        with pytest.warns(UserWarning, match="corrupt"):
            assert autotune.TuningCache.load(str(p)) is None
        # and the planners still work through load_cache on the bad file
        with pytest.warns(UserWarning, match="corrupt"):
            autotune.load_cache(str(p))
        plan = plan_lib.plan_packed_matmul(8, 32, 64, SPEC, backend="xla")
        assert plan.source == "heuristic"

    def test_stale_schema_warns_and_falls_back(self, tmp_path):
        p = tmp_path / "old.json"
        p.write_text(json.dumps({"schema": autotune.SCHEMA_VERSION + 1,
                                 "device": "cpu", "entries": {}}))
        with pytest.warns(UserWarning, match="schema"):
            assert autotune.TuningCache.load(str(p)) is None

    def test_entries_must_be_a_dict(self, tmp_path):
        p = tmp_path / "flat.json"
        p.write_text(json.dumps({"schema": autotune.SCHEMA_VERSION,
                                 "device": "cpu", "entries": [1, 2]}))
        with pytest.warns(UserWarning, match="entries"):
            assert autotune.TuningCache.load(str(p)) is None


class TestPlannerConsultation:
    def test_hit_returns_cache_backed_plan(self):
        c = _empty()
        c.store(autotune.matmul_key(8, 32, 64, SPEC, backend="xla"),
                {"block_m": 32, "block_n": 128, "chunks": 2})
        plan = plan_lib.plan_packed_matmul(8, 32, 64, SPEC, backend="xla")
        assert plan.source == "tuned"
        assert (plan.block_m, plan.block_n, plan.chunks) == (32, 128, 2)
        # vmem estimate recomputed from the planner's own accounting
        assert plan.vmem_bytes == plan_lib.matmul_working_set(32, 128, 2,
                                                              SPEC)

    def test_miss_falls_back_to_heuristic(self):
        _empty()
        plan = plan_lib.plan_packed_matmul(8, 32, 64, SPEC, backend="xla")
        assert plan.source == "heuristic"
        # TPU-aligned blocks: rows rounded to 8, N and K in 128 lanes
        assert (plan.block_m, plan.block_n, plan.chunks) == (8, 128, 1)

    def test_use_tuning_cache_false_bypasses_hit(self):
        c = _empty()
        c.store(autotune.matmul_key(8, 32, 64, SPEC, backend="xla"),
                {"block_m": 32, "block_n": 128, "chunks": 2})
        plan = plan_lib.plan_packed_matmul(8, 32, 64, SPEC, backend="xla",
                                           use_tuning_cache=False)
        assert plan.source == "heuristic"

    def test_over_budget_entry_ignored(self):
        c = _empty()
        c.store(autotune.matmul_key(8, 32, 64, SPEC, backend="xla"),
                {"block_m": 4096, "block_n": 4096, "chunks": 16})
        plan = plan_lib.plan_packed_matmul(8, 32, 64, SPEC, backend="xla")
        assert plan.source == "heuristic"

    def test_misaligned_entry_ignored(self):
        """A tuned tile the TPU compiler would refuse (N block not a
        multiple of 128 lanes) never becomes a plan."""
        c = _empty()
        c.store(autotune.matmul_key(8, 32, 64, SPEC, backend="xla"),
                {"block_m": 32, "block_n": 64, "chunks": 2})
        plan = plan_lib.plan_packed_matmul(8, 32, 64, SPEC, backend="xla")
        assert plan.source == "heuristic"
        assert plan_lib.matmul_tiles_ok(plan.block_m, plan.block_n,
                                        plan.chunks)

    def test_malformed_entry_ignored(self):
        c = _empty()
        c.store(autotune.matmul_key(8, 32, 64, SPEC, backend="xla"),
                {"block_m": "huge"})
        plan = plan_lib.plan_packed_matmul(8, 32, 64, SPEC, backend="xla")
        assert plan.source == "heuristic"

    def test_conv_hit_and_pinned_tiles_bypass(self):
        c = _empty()
        x_shape, w_shape = (1, 32, 32, 8), (3, 3, 8, 16)
        c.store(autotune.conv2d_key(x_shape, w_shape, SPEC,
                                    padding="VALID", backend="xla"),
                {"block_h": 4, "block_co": 16})
        plan = plan_lib.plan_packed_conv2d(x_shape, w_shape, SPEC,
                                           padding="VALID", backend="xla")
        assert plan.source == "tuned"
        assert (plan.block_h, plan.block_co) == (4, 16)
        pinned = plan_lib.plan_packed_conv2d(x_shape, w_shape, SPEC,
                                             padding="VALID", backend="xla",
                                             block_h=8)
        assert pinned.source == "heuristic" and pinned.block_h == 8

    def test_plan_selection_deterministic_given_fixed_cache(self):
        c = _empty()
        c.store(autotune.matmul_key(8, 32, 64, SPEC, backend="xla"),
                {"block_m": 16, "block_n": 128, "chunks": 4})
        a = plan_lib.plan_packed_matmul(8, 32, 64, SPEC, backend="xla")
        plan_lib.clear_plan_cache()
        b = plan_lib.plan_packed_matmul(8, 32, 64, SPEC, backend="xla")
        assert a == b  # same frozen plan after a cold planner cache

    def test_attention_chunk_lookup(self):
        c = _empty()
        c.store(autotune.attention_key(2, 64, 64, 4, 2, 16, 0),
                {"q_chunk": 32})
        assert autotune.attention_chunk_for(2, 64, 64, 4, 2, 16, 0) == 32
        assert autotune.attention_chunk_for(1, 1, 1, 1, 1, 1, 0) == 512


class TestTuners:
    def test_tune_matmul_stores_winner_and_plan_adopts_it(self):
        cache = _empty()
        entry = autotune.tune_packed_matmul(4, 8, 16, SPEC, backend="xla",
                                            repeats=1, max_candidates=3)
        for k in ("block_m", "block_n", "chunks", "wall_us",
                  "heuristic_us", "vmem_bytes", "candidates"):
            assert k in entry, k
        key = autotune.matmul_key(4, 8, 16, SPEC, backend="xla")
        assert cache.lookup(key) is entry
        plan = plan_lib.plan_packed_matmul(4, 8, 16, SPEC, backend="xla")
        assert plan.source == "tuned"
        assert plan.block_m == entry["block_m"]
        # re-tune is a cache hit, not a re-measure
        again = autotune.tune_packed_matmul(4, 8, 16, SPEC, backend="xla")
        assert again is entry

    def test_tuned_plan_stays_bit_exact(self):
        _empty()
        rng = np.random.default_rng(0)
        q_a = jnp.asarray(rng.integers(0, 4, (5, 40)), jnp.int32)
        q_w = jnp.asarray(rng.integers(0, 4, (40, 16)), jnp.int32)
        from repro.core import packing
        ap = packing.pack_activations(q_a, SPEC, -1)
        wp = packing.pack_weights(q_w, SPEC, 0)
        autotune.tune_packed_matmul(5, ap.shape[-1], 16, SPEC,
                                    backend="pallas", repeats=1,
                                    max_candidates=3)
        plan = plan_lib.plan_packed_matmul(5, ap.shape[-1], 16, SPEC,
                                           backend="pallas")
        assert plan.source == "tuned"
        got = ops.packed_matmul(ap, wp, SPEC, plan=plan)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(ref.matmul_i32_ref(q_a,
                                                                    q_w)))

    def test_tune_conv2d_stores_winner(self):
        cache = _empty()
        entry = autotune.tune_packed_conv2d(
            (1, 12, 12, 4), (3, 3, 4, 8), SPEC, padding="VALID",
            backend="xla", repeats=1, max_candidates=3)
        assert "block_h" in entry and "block_co" in entry
        key = autotune.conv2d_key((1, 12, 12, 4), (3, 3, 4, 8), SPEC,
                                  padding="VALID", backend="xla")
        assert cache.lookup(key) is entry

    def test_store_into_active_cache_invalidates_memoized_plans(self):
        _empty()
        before = plan_lib.plan_packed_matmul(4, 8, 16, SPEC, backend="xla")
        assert before.source == "heuristic"
        autotune.tune_packed_matmul(4, 8, 16, SPEC, backend="xla",
                                    repeats=1, max_candidates=2)
        after = plan_lib.plan_packed_matmul(4, 8, 16, SPEC, backend="xla")
        assert after.source == "tuned"


class TestLayoutTuner:
    """Lane-layout sweep (DESIGN.md §16): the tuner stores a verified
    winner, resolution defaults to the config spec on miss/mismatch, and a
    pinned non-default winner flows pack -> plan -> dispatch bit-exactly."""

    def test_tune_matmul_layout_stores_verified_winner(self):
        cache = _empty()
        entry = autotune.tune_matmul_layout(4, 40, 16, SPEC, backend="xla",
                                            repeats=1, max_candidates=3)
        for field in ("spec", "wall_us", "base_spec", "base_us",
                      "candidates"):
            assert field in entry, field
        assert entry["base_spec"] == str(SPEC)
        assert entry["candidates"] >= 2      # family swept, not just base
        chosen = PackSpec.parse(entry["spec"])
        assert chosen.feasible
        key = autotune.matmul_layout_key(40, 16, 2, 2, backend="xla")
        assert cache.lookup(key) is entry
        # resolution returns the stored winner; re-tune is a cache hit
        assert autotune.matmul_layout_for(40, 16, SPEC,
                                          backend="xla") == chosen
        assert autotune.tune_matmul_layout(4, 40, 16, SPEC,
                                           backend="xla") is entry

    def test_tune_conv2d_layout_stores_verified_winner(self):
        cache = _empty()
        entry = autotune.tune_conv2d_layout(
            (1, 10, 10, 4), (3, 3, 4, 8), SPEC, padding="VALID",
            backend="xla", repeats=1, max_candidates=3)
        chosen = PackSpec.parse(entry["spec"])
        assert chosen.feasible
        key = autotune.conv2d_layout_key((1, 10, 10, 4), (3, 3, 4, 8), 2, 2,
                                         padding="VALID", backend="xla")
        assert cache.lookup(key) is entry
        assert autotune.conv2d_layout_for(
            (1, 10, 10, 4), (3, 3, 4, 8), SPEC, padding="VALID",
            backend="xla") == chosen

    def test_layout_for_defaults_to_base_on_miss(self):
        _empty()
        assert autotune.matmul_layout_for(40, 16, SPEC,
                                          backend="xla") == SPEC
        assert autotune.conv2d_layout_for(
            (1, 8, 8, 4), (3, 3, 4, 8), SPEC, padding="VALID",
            backend="xla") == SPEC

    def test_layout_for_ignores_unusable_entries(self):
        cache = _empty()
        key = autotune.matmul_layout_key(40, 16, 2, 2, backend="xla")
        for bad in ({"spec": "W4A4/int16xP2s8"},   # wrong bits + infeasible
                    {"spec": "garbage"},
                    {"wall_us": 3.0}):
            cache.store(key, bad)
            assert autotune.matmul_layout_for(40, 16, SPEC,
                                              backend="xla") == SPEC

    def test_layout_key_excludes_rows(self):
        # Weights pack once and serve every batch size: the layout choice
        # may not depend on m.
        k1 = autotune.matmul_layout_key(40, 16, 2, 2, backend="xla")
        assert "m=" not in k1 and "k=40" in k1 and "n=16" in k1

    def test_chosen_layout_flows_pack_plan_dispatch(self):
        """Pin a non-default winner; pack_dense_params packs under it,
        build_layer_plans plans under it, dense_apply dispatches under it —
        bit-exact against the float reference path's quantized result."""
        from repro.core.quant import QuantConfig
        from repro.models import common
        from repro.serve import prepare

        cache = _empty()
        qcfg = QuantConfig(enabled=True, w_bits=2, a_bits=2)
        k, n = 32, 16
        wide = PackSpec(2, 2, jnp.int32.dtype, shift=16)
        backend = plan_lib.resolve_backend("auto")
        cache.store(autotune.matmul_layout_key(k, n, 2, 2, backend=backend),
                    {"spec": str(wide)})

        rng = np.random.default_rng(7)
        p = {"kernel": jnp.asarray(rng.normal(size=(k, n)) * 0.1,
                                   jnp.float32)}
        packed = common.pack_dense_params(p, qcfg)
        assert packed["w_packed"].dtype == wide.lane_dtype
        assert packed["w_packed"].shape[0] == -(-k // wide.n_pack)

        class Cfg:
            quant = qcfg
        plans = prepare.build_layer_plans({"mlp": packed}, Cfg(),
                                          batch_rows=4)
        assert plans["mlp"].spec == wide

        x = jnp.asarray(rng.normal(size=(4, k)), jnp.float32)
        y = common.dense_apply(packed, x, qcfg=qcfg, quant_mode="packed",
                               compute_dtype=jnp.float32)
        # same quantized result as packing under the config default
        base_packed = common.pack_dense_params(p, qcfg, spec=SPEC)
        y_base = common.dense_apply(base_packed, x, qcfg=qcfg,
                                    quant_mode="packed",
                                    compute_dtype=jnp.float32)
        assert base_packed["w_packed"].dtype == SPEC.lane_dtype
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_base),
                                   rtol=1e-5, atol=1e-5)

    def test_stale_layout_cache_falls_back_on_packed_evidence(self):
        """Bytes packed under the default, cache later says int32/s16: the
        packed leaf contradicts the resolved layout, so dispatch falls back
        to the layout the bytes actually use instead of misreading them."""
        from repro.core.quant import QuantConfig
        from repro.models import common

        cache = _empty()
        qcfg = QuantConfig(enabled=True, w_bits=2, a_bits=2)
        k, n = 32, 16
        rng = np.random.default_rng(3)
        p = {"kernel": jnp.asarray(rng.normal(size=(k, n)) * 0.1,
                                   jnp.float32)}
        packed = common.pack_dense_params(p, qcfg)   # default layout
        backend = plan_lib.resolve_backend("auto")
        cache.store(autotune.matmul_layout_key(k, n, 2, 2, backend=backend),
                    {"spec": str(PackSpec(2, 2, jnp.int32.dtype, shift=16))})
        spec = common.dense_layer_spec(k, n, qcfg,
                                       w_packed=packed["w_packed"])
        assert spec == SPEC
        x = jnp.asarray(rng.normal(size=(2, k)), jnp.float32)
        y = common.dense_apply(packed, x, qcfg=qcfg, quant_mode="packed",
                               compute_dtype=jnp.float32)
        assert np.isfinite(np.asarray(y)).all()


class TestMeasure:
    def test_median_of_repeats_scales_batch_to_min_time(self):
        calls = []

        def fn():
            calls.append(1)
            return jnp.zeros(())

        us = autotune.measure_us(fn, repeats=3, min_time_s=0.001, iters=1)
        assert us > 0
        # warmup + calibration doubling + repeat batches all landed
        assert len(calls) >= 4

    def test_zero_min_time_keeps_fixed_iters(self):
        calls = []

        def fn():
            calls.append(1)
            return jnp.zeros(())

        autotune.measure_us(fn, repeats=2, min_time_s=0.0, iters=3,
                            warmup=1)
        assert len(calls) == 1 + 3 + 3


@pytest.mark.skipif(jax.default_backend() != "cpu",
                    reason="committed tuning cache is CPU-scoped")
class TestCommittedCache:
    """Acceptance: with the committed CPU cache, the planners return
    cache-backed plans for the benchmarked signatures."""

    def test_committed_cache_loads(self):
        path = autotune.default_cache_path("cpu")
        cache = autotune.TuningCache.load(path)
        assert cache is not None, path
        assert cache.device == "cpu"
        assert cache.entries

    def test_planners_return_cache_backed_plans(self):
        mm = plan_lib.plan_packed_matmul(8, 128, 256, SPEC,
                                         backend="pallas")
        assert mm.source == "tuned"
        conv = plan_lib.plan_packed_conv2d(
            (1, 64, 64, 16), (7, 7, 16, 32), SPEC, padding="VALID",
            backend="pallas")
        assert conv.source == "tuned"
