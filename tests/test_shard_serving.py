"""Mesh-native serving (serve/shard.ShardPlan, DESIGN.md §15).

The tentpole invariant: on a forced multi-device CPU mesh
(XLA_FLAGS=--xla_force_host_platform_device_count=4, the `shard` CI lane),
the tensor-parallel packed ServingEngine produces token-for-token identical
output to the single-device engine — the packed integer algebra is exact,
column-parallel N-sharding keeps every int32 word / int16 lane shard-local,
and the kv-head-sharded (possibly sub-byte packed) cache quantizes and
packs per head.  A mesh=1 engine is behaviorally unchanged.

Multi-device tests skip below 4 devices so the plain tier-1 run stays
green on 1-device hosts; the warning/spec tests run anywhere.
"""

import subprocess
import sys
import textwrap
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.core.quant import QuantConfig
from repro.launch.mesh import make_host_mesh, make_serving_mesh
from repro.models import lm
from repro.parallel import sharding
from repro.serve.config import EngineConfig
from repro.serve.engine import Request, ServingEngine
from repro.serve.shard import ShardPlan
from test_pipeline import subprocess_env

pytestmark = pytest.mark.shard

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs 4 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")


def packed_cfg(name="stablelm-1.6b", w_bits=2, kv_bits=4, **kw):
    lane = "int32" if w_bits >= 4 else "int16"   # w4a4 overflows int16 lanes
    return configs.get_config(name, reduced=True).replace(
        param_dtype="float32", compute_dtype="float32",
        quant=QuantConfig(enabled=True, w_bits=w_bits, a_bits=w_bits,
                          lane_dtype=lane, kv_bits=kv_bits), **kw)


def run_engine(cfg, params, mesh, *, prompts, max_new=5, **kw):
    eng = ServingEngine(cfg, params, mesh=mesh, config=EngineConfig(
        max_batch=2, max_len=32, packed=True, prefill_chunk=4, **kw))
    for i, p in enumerate(prompts):
        assert eng.submit(Request(uid=i, prompt=p, max_new_tokens=max_new))
    return eng, {r.uid: tuple(r.output) for r in eng.run_to_completion()}


def seeded_prompts(cfg, lens=(7, 3, 11), seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in lens]


# ---------------------------------------------------------------------------
# Tentpole: token-for-token identity, sharded vs single-device
# ---------------------------------------------------------------------------

@needs_mesh
@pytest.mark.parametrize("w_bits,kv_bits", [(2, 0), (2, 4), (4, 0), (4, 4)])
def test_sharded_engine_token_identical(w_bits, kv_bits):
    """4-way TP packed engine == single-device engine, token for token,
    across packed 2/4-bit weights x kv_bits {16, 4} (staggered admission
    included: three prompts through two slots)."""
    cfg = packed_cfg(w_bits=w_bits, kv_bits=kv_bits)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompts = seeded_prompts(cfg)
    _, single = run_engine(cfg, params, None, prompts=prompts)
    eng, sharded = run_engine(cfg, params, make_serving_mesh(4),
                              prompts=prompts)
    assert sharded == single
    # and the layout actually sharded: column-parallel packed weights,
    # kv-head-sharded cache (words axis intact for packed caches)
    wq = eng.params["layers"][0]["attn"]["q"]["w_packed"]
    assert wq.sharding.spec == P(None, "model")
    assert wq.addressable_shards[0].data.shape == (wq.shape[0],
                                                   wq.shape[1] // 4)
    kc = eng.caches[0]["attn"]["k"]
    assert kc.sharding.spec == P(None, None, "model") \
        or kc.sharding.spec == P(None, None, "model", None)
    assert kc.addressable_shards[0].data.shape[2] == kc.shape[2] // 4


@needs_mesh
def test_sharded_engine_gqa_indivisible_heads_replicate():
    """granite (reduced: 2 kv heads) on a 4-way mesh: the divisibility
    guard replicates the cache head axis rather than producing an invalid
    sharding, and output stays token-identical."""
    cfg = packed_cfg("granite-3-8b", kv_bits=4)
    params = lm.init_params(jax.random.PRNGKey(1), cfg)
    prompts = seeded_prompts(cfg, seed=2)
    _, single = run_engine(cfg, params, None, prompts=prompts)
    eng, sharded = run_engine(cfg, params, make_serving_mesh(4),
                              prompts=prompts)
    assert sharded == single
    kc = eng.caches[0]["attn"]["k"]
    assert all(a is None for a in kc.sharding.spec)


@needs_mesh
def test_mesh1_engine_behaviorally_unchanged():
    """A mesh with model=1 degrades to the single-device layout (every
    spec guards to replicated) and generates identical tokens."""
    cfg = packed_cfg()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompts = seeded_prompts(cfg)
    _, single = run_engine(cfg, params, None, prompts=prompts)
    eng, mesh1 = run_engine(cfg, params, make_serving_mesh(1),
                            prompts=prompts)
    assert mesh1 == single
    assert eng.shard_plan.model_shards == 1


@needs_mesh
def test_sharded_engine_metrics_and_reports():
    """The sharded engine's metrics report carries the new per-request
    latency distributions and the capacity report names the shard plan."""
    cfg = packed_cfg()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    eng, _ = run_engine(cfg, params, make_serving_mesh(4),
                        prompts=seeded_prompts(cfg), max_new=3)
    rep = eng.metrics.report()
    assert len(eng.metrics.ttft_s) == 3          # one sample per request
    assert len(eng.metrics.tpot_s) == 3
    assert rep["ttft_s"]["p95"] >= rep["ttft_s"]["p50"] > 0
    assert rep["tpot_s"]["mean"] > 0
    cap = eng.capacity_report()
    assert cap["shard_plan"]["model_shards"] == 4
    assert cap["shard_plan"]["mesh"] == {"data": 1, "model": 4}


# ---------------------------------------------------------------------------
# cache_shardings over quantized caches (satellite)
# ---------------------------------------------------------------------------

@needs_mesh
@pytest.mark.parametrize("kv_bits", [4, 2])
def test_cache_shardings_quantized_kv_head_shard(kv_bits):
    """cache_shardings(kv_head_shard=True) on a real 4-device host mesh
    over sub-byte packed caches: K/V int32 words and the per-(pos, head)
    scale planes shard the kv-head axis, placement round-trips values,
    and every shard holds whole words."""
    cfg = packed_cfg(kv_bits=kv_bits)
    mesh = make_host_mesh(data=1, model=4)
    caches = lm.init_caches(cfg, 2, 16, dtype=jnp.float32)
    assert caches[0]["attn"]["k"].dtype == jnp.int32    # packed words
    sh = sharding.cache_shardings(caches, cfg, mesh, 2, kv_head_shard=True)
    attn = sh[0]["attn"]
    bp = ("data",)       # size-1 batch axis on the (1, 4) serving mesh
    assert attn["k"].spec == P(bp, None, "model", None)
    assert attn["v"].spec == P(bp, None, "model", None)
    assert attn["k_scale"].spec == P(bp, None, "model")
    assert attn["v_scale"].spec == P(bp, None, "model")
    placed = jax.tree.map(
        lambda c, s: None if c is None else jax.device_put(c, s),
        caches, sh, is_leaf=lambda x: x is None)
    kvh, words = caches[0]["attn"]["k"].shape[2:]
    shard_shape = placed[0]["attn"]["k"].addressable_shards[0].data.shape
    assert shard_shape[2] == kvh // 4 and shard_shape[3] == words
    np.testing.assert_array_equal(np.asarray(placed[0]["attn"]["k"]),
                                  np.asarray(caches[0]["attn"]["k"]))


@needs_mesh
def test_cache_shardings_quantized_scales_follow_heads():
    """Writing through the sharded quantized cache keeps values identical
    to the unsharded write (quantize/pack is per-(pos, head) local)."""
    from repro.models import attention
    cfg = packed_cfg(kv_bits=4)
    mesh = make_host_mesh(data=1, model=4)
    caches = lm.init_caches(cfg, 2, 16, dtype=jnp.float32)[0]["attn"]
    sh = sharding.cache_shardings(
        [{"attn": caches}], cfg, mesh, 2, kv_head_shard=True)[0]["attn"]
    placed = jax.tree.map(jax.device_put, caches, sh)
    rng = np.random.default_rng(3)
    hd = cfg.resolved_head_dim
    k = jnp.asarray(rng.normal(size=(2, 1, cfg.num_kv_heads, hd)),
                    jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 1, cfg.num_kv_heads, hd)),
                    jnp.float32)
    ref = attention._cache_write(caches, k, v, 0, 4)
    got = attention._cache_write(placed, k, v, 0, 4)
    for key in ("k", "v", "k_scale", "v_scale"):
        np.testing.assert_array_equal(np.asarray(got[key]),
                                      np.asarray(ref[key]))


# ---------------------------------------------------------------------------
# ShardPlan specs (no mesh-size requirement beyond what the host has)
# ---------------------------------------------------------------------------

@needs_mesh
def test_shard_plan_param_specs():
    """Packed serving tree: w_packed/w_dense/bias/col_sums shard the
    output axis; quant scalars and unpacked leaves replicate; indivisible
    dims guard to replicated."""
    from repro.serve.prepare import prepare_serving_params
    cfg = packed_cfg()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    packed = prepare_serving_params(params, cfg)
    plan = ShardPlan(make_serving_mesh(4))
    sh = plan.param_shardings(packed)
    q = sh["layers"][0]["attn"]["q"]
    assert q["w_packed"].spec == P(None, "model")
    assert q["col_sums"].spec == P("model")
    assert q["w_scale"].spec == P()
    assert sh["embed"]["table"].spec == P(None, None)
    # local-shape planning: the per-shard matmul plans against N/4
    n = packed["layers"][0]["attn"]["q"]["w_packed"].shape[-1]
    assert plan.local_out(n) == n // 4
    assert plan.local_out(n - 1) == n - 1          # indivisible: unsharded


@needs_mesh
def test_sharded_plans_cover_dispatch_signatures():
    """Under a ShardPlan, build_layer_plans keeps per-shard local plans as
    the primary entries AND pre-memoizes the global-width signatures the
    GSPMD-jitted steps re-plan with at trace time: the plan the dispatch
    path looks up must be the exact init-built ``@global`` object (the
    memoized planner guarantees identity), so autotune warm-tuning covers
    what execution actually reads."""
    from repro.core.packing import PackSpec
    from repro.kernels import plan as plan_lib
    cfg = packed_cfg()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, mesh=make_serving_mesh(4),
                        config=EngineConfig(max_batch=2, max_len=32,
                                            packed=True, prefill_chunk=4))
    spec = PackSpec.from_config(cfg.quant)
    node = eng.params["layers"][0]["attn"]["q"]
    kp, n_global = node["w_packed"].shape      # sharded arrays: global shape
    assert "layers[0]/attn/q@global" in eng.plans
    # what ops.quantized_linear(plan=None) looks up inside the jitted
    # decode step: rows = max_batch, global n, backend 'auto' (kwargs
    # spelled exactly as quantized_linear spells them — lru_cache keys
    # include explicit kwargs)
    dispatched = plan_lib.plan_packed_matmul(
        2, kp, n_global, spec, backend="auto", weight_store="lanes",
        k_full=None)
    assert dispatched is eng.plans["layers[0]/attn/q@global"]
    prefill = plan_lib.plan_packed_matmul(
        2 * 4, kp, n_global, spec, backend="auto", weight_store="lanes",
        k_full=None)
    assert prefill is eng.plans["layers[0]/attn/q@global@prefill"]


def test_host_mesh_clamp_warns():
    """make_host_mesh refuses a mesh larger than the host, naming the
    requested vs actual shape (a clamped mesh would run an "N-way" layout
    on fewer devices); feasible requests stay silent."""
    n = len(jax.devices())
    with pytest.raises(ValueError, match=rf"requested \(data={2 * n}, "
                                         rf"model=4\).*has {n}"):
        make_host_mesh(data=2 * n, model=4)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        mesh = make_host_mesh(data=1, model=1)     # always feasible
    assert dict(mesh.shape) == {"data": 1, "model": 1}


def test_serving_mesh_validates():
    with pytest.raises(ValueError):
        make_serving_mesh(0)


# ---------------------------------------------------------------------------
# Tier-1 guard: 4-way tensor-parallel serving on forced host devices
# ---------------------------------------------------------------------------

TP_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro import configs
    from repro.kernels import ops, ulppack_attention
    from repro.kernels import plan as plan_lib
    from repro.launch import serve
    from repro.launch.mesh import make_serving_mesh
    from repro.models import lm
    from repro.parallel.sharding import activation_mesh
    from repro.serve.config import EngineConfig
    from repro.serve.engine import Request, ServingEngine

    argv = ["--arch", "stablelm-1.6b", "--reduced", "--model-parallel", "4",
            "--kv-bits", "4", "--paged-kv", "--max-batch", "2",
            "--max-len", "48", "--prefill-chunk", "8", "--requests", "3",
            "--max-new-tokens", "6", "--prompt-len", "9"]
    sys.argv = ["serve"] + argv
    serve.main()                          # the CLI path end to end

    args = serve.build_parser().parse_args(argv)
    cfg = configs.get_config(args.arch, reduced=True)
    cfg = cfg.replace(quant=cfg.quant.replace(kv_bits=args.kv_bits))
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (9, 23, 4)]

    def tokens(mesh):
        eng = ServingEngine(cfg, params, mesh=mesh,
                            config=EngineConfig.from_args(args))
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=p, max_new_tokens=6))
        return {r.uid: r.output for r in eng.run_to_completion()}

    single = tokens(None)
    tp = tokens(make_serving_mesh(args.model_parallel))
    assert tp == single, (tp, single)

    # the Pallas kernels run per 'model' shard under shard_map (interpreted
    # here) and agree with the one-device kernels
    mesh = make_serving_mesh(4)
    spec = ops.PackSpec(2, 2, jnp.int16)
    a = ops.packing.pack_activations(
        jnp.asarray(rng.integers(0, 4, (8, 64)), jnp.int32), spec)
    w = ops.packing.pack_weights(
        jnp.asarray(rng.integers(0, 4, (64, 512)), jnp.int32), spec)
    plan = plan_lib.plan_packed_matmul(8, 32, 512, spec, backend="pallas")
    one = ops.packed_matmul(a, w, spec, plan=plan)
    with activation_mesh(mesh):
        sharded = jax.jit(lambda a, w: ops.packed_matmul(
            a, w, spec, plan=plan))(a, w)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(sharded))

    b, h, hd, ps, n_pages = 2, 8, 16, 16, 3
    pool = b * n_pages
    cache = {
        "k": jnp.asarray(rng.integers(-2**31, 2**31, (pool, ps, h, 2)),
                         jnp.int32),
        "v": jnp.asarray(rng.integers(-2**31, 2**31, (pool, ps, h, 2)),
                         jnp.int32),
        "k_scale": jnp.asarray(rng.uniform(.02, .2, (pool, ps, h)),
                               jnp.bfloat16),
        "v_scale": jnp.asarray(rng.uniform(.02, .2, (pool, ps, h)),
                               jnp.bfloat16)}
    bt = jnp.asarray(rng.permutation(pool).reshape(b, n_pages), jnp.int32)
    valid = jnp.asarray([40, 17], jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, 1, h, hd)), jnp.bfloat16)

    def attend(q, cache, valid, bt, axis):
        return ulppack_attention.fused_decode_attention(
            q, cache, valid, valid[:, None] - 1, kv_bits=4, hd=hd,
            block_tables=bt, backend="pallas", shard_axis=axis)

    one = attend(q, cache, valid, bt, None)
    with activation_mesh(mesh):
        sharded = jax.jit(lambda *xs: attend(*xs, "model"))(
            q, cache, valid, bt)
    np.testing.assert_array_equal(np.asarray(one, np.float32),
                                  np.asarray(sharded, np.float32))
    print("TP_OK", len(jax.devices()))
""")


def test_tensor_parallel_serving_cli_subprocess():
    """Reduced stablelm-1.6b at --model-parallel 4 on four forced host
    devices: the CLI runs, greedy tokens equal the one-device engine's
    (bf16 compute, paged 4-bit KV), and the Pallas matmul and decode
    attention, run per shard under shard_map, equal their one-device
    results.  Runs in a subprocess so the device-count flag stays there —
    the in-process shard tests skip on a one-device tier-1 run."""
    r = subprocess.run([sys.executable, "-c", TP_SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       env=subprocess_env())
    assert "TP_OK 4" in r.stdout, (r.stdout[-2000:], r.stderr[-3000:])
