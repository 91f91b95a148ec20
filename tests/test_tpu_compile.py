"""Compile the serving kernels for a described TPU v5e (no chip needed).

Each test lowers and compiles one Pallas kernel the serving path runs, at
stablelm-1.6b's widths (d 2048, d_ff 5632, 32 heads of 64, W2A2 int16xP2s8
lanes, paged 4- and 2-bit KV), with ``interpret=False``, for a v5e chip
that JAX describes from its topology name — the TPU compiler refuses here
what it would refuse on the chip (unaligned blocks, operand types Mosaic
cannot feed the MXU, shapes it cannot relayout).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and under pytest-xdist every
worker imports this module.  Keep these tests in this one file.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core.packing import PackSpec
from repro.kernels import ops  # noqa: F401 (registers the backends)
from repro.kernels import plan as plan_lib
from repro.kernels import ulppack_attention

CFG = configs.get_config("stablelm-1.6b")
SPEC = PackSpec.from_config(CFG.quant)
D, FF = CFG.d_model, CFG.d_ff
H, KVH, HD = CFG.num_heads, CFG.num_kv_heads, CFG.resolved_head_dim


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def chip(plan):
    assert plan.backend == "pallas"
    return dataclasses.replace(plan, interpret=False)


@pytest.mark.parametrize("rows", [8, 256], ids=["decode8", "prefill256"])
@pytest.mark.parametrize("k,n", [(D, D), (D, FF), (FF, D)],
                         ids=["2048-2048", "2048-5632", "5632-2048"])
def test_packed_matmul_compiles(one_chip, rows, k, n):
    kp = k // SPEC.n_pack
    plan = chip(plan_lib.plan_packed_matmul(rows, kp, n, SPEC,
                                            backend="pallas"))
    assert plan_lib.matmul_tiles_ok(plan.block_m, plan.block_n, plan.chunks)
    a = jax.ShapeDtypeStruct((rows, kp), SPEC.lane_dtype, sharding=one_chip)
    w = jax.ShapeDtypeStruct((kp, n), SPEC.lane_dtype, sharding=one_chip)
    txt = compiled_text(lambda a, w: plan_lib.dispatch(plan, a, w), a, w)
    assert "tpu_custom_call" in txt


def test_quantize_pack_compiles(one_chip):
    rows = 8
    plan = chip(plan_lib.plan_quantize_pack(rows, D, SPEC, backend="pallas"))
    x = jax.ShapeDtypeStruct((rows, D), jnp.float32, sharding=one_chip)
    s = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    z = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    txt = compiled_text(lambda x, s, z: plan_lib.dispatch(plan, x, s, z),
                        x, s, z)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("kv_bits", [4, 2])
def test_paged_attention_decode_compiles(one_chip, kv_bits):
    b, page, n_pages = 8, 16, 34          # 544-token slots, 16-row pages
    pool = b * n_pages
    words = -(-HD // (32 // kv_bits))

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    cache = {"k": sds((pool, page, KVH, words), jnp.int32),
             "v": sds((pool, page, KVH, words), jnp.int32),
             "k_scale": sds((pool, page, KVH), jnp.bfloat16),
             "v_scale": sds((pool, page, KVH), jnp.bfloat16)}
    plan = chip(plan_lib.plan_attention_decode(
        b, n_pages * page, H, KVH, HD, kv_bits, page_size=page,
        backend="pallas"))

    def attend(q, cache, valid, qpos, bt):
        return ulppack_attention.fused_decode_attention(
            q, cache, valid, qpos, kv_bits=kv_bits, hd=HD, plan=plan,
            block_tables=bt)

    txt = compiled_text(attend, sds((b, 1, H, HD), jnp.bfloat16), cache,
                        sds((b,), jnp.int32), sds((b, 1), jnp.int32),
                        sds((b, n_pages), jnp.int32))
    assert "tpu_custom_call" in txt
