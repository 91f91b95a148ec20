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
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core.packing import PackSpec
from repro.kernels import ops  # noqa: F401 (registers the backends)
from repro.kernels import plan as plan_lib
from repro.kernels import ulppack_attention
from repro.models import attention

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

    cache = {"k": sds((pool, page, KVH * words), jnp.int32),
             "v": sds((pool, page, KVH * words), jnp.int32),
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


@pytest.mark.parametrize("kv_bits", [4, 2])
def test_paged_decode_writes_the_pool_in_place(one_chip, kv_bits):
    """One layer of the decode step at the chat cell's pool (1536 pages
    of 16 rows, 16 rows in the batch, 64 table entries), caches donated:
    the paged write then the attention kernel.  The word leaves enter in
    the row-major layout that the scatter and the kernel both read, no
    copy of a whole word leaf is left, synchronous or staged ahead into
    VMEM (a 4-D [P, ps, KVH, words] pool took three per leaf: into the
    scatter's layout, back, and into the kernel's flat view; the kernel
    reads only live pages of a pool kept in HBM), and every pool leaf is
    aliased to its output."""
    b, page, n_pages, pool = 16, 16, 64, 1536
    cfg = CFG.replace(quant=dataclasses.replace(CFG.quant, kv_bits=kv_bits))

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    cache = jax.tree.map(lambda x: sds(x.shape, x.dtype), jax.eval_shape(
        lambda: attention.init_paged_kv_cache(cfg, pool, page)))
    leaf = cache["k"].shape
    plan = chip(plan_lib.plan_attention_decode(
        b, n_pages * page, H, KVH, HD, kv_bits, page_size=page,
        backend="pallas"))

    def step(q, k, v, cache, index, bt):
        wpos = index[:, None]
        phys = jnp.take_along_axis(bt, wpos // page, axis=1)
        cache = attention._cache_write_paged(
            cache, k, v, phys, wpos % page, jnp.ones((b, 1), bool),
            kv_bits)
        out = ulppack_attention.fused_decode_attention(
            q, cache, index + 1, wpos, kv_bits=kv_bits, hd=HD, plan=plan,
            block_tables=bt)
        return out, cache

    rows = sds((b, 1, KVH, HD), jnp.bfloat16)
    txt = jax.jit(step, donate_argnums=(3,)).lower(
        sds((b, 1, H, HD), jnp.bfloat16), rows, rows, cache,
        sds((b,), jnp.int32), sds((b, n_pages), jnp.int32)
    ).compile().as_text()
    head = txt.splitlines()[0]
    entry = re.search(r"entry_computation_layout=\{\((.*?)\)->", head)[1]
    word = re.compile(r"s32\[(\d+(?:,\d+)*)\](\{[^}]*\})")
    layouts = [lay for dims, lay in word.findall(entry)
               if math.prod(map(int, dims.split(","))) == math.prod(leaf)]
    assert len(layouts) == 2, entry
    assert all(lay.startswith("{2,1,0") for lay in layouts), layouts
    copies = [ln for ln in txt.splitlines()
              if re.search(r"= \(?s32\[[\d,]+\]\{[^}]*\}.* copy(-start)?\(",
                           ln)
              and math.prod(map(int, word.search(ln)[1].split(",")))
              == math.prod(leaf)]
    assert not copies, copies[:3]
    # flattened arguments: q 0, k 1, v 2, the pool's leaves 3-6 (sorted
    # keys k, k_scale, v, v_scale), index 7, table 8
    aliased = {int(p) for p in re.findall(
        r"\{\d*\}: \((\d+), \{\}", head)}
    assert {3, 4, 5, 6} <= aliased, head[:400]


def test_paged_decode_kernel_size_does_not_grow_with_chunks(one_chip):
    """The paged kernel copies a step's pages in a loop, so its Mosaic
    module is about the same size at 2 and at 8 pages per grid step: a
    body unrolled over pages would grow every layer's lowering, in every
    process that serves."""
    b, page, n_pages = 16, 16, 96
    words = HD // 8                                    # 4-bit KV

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    cache = {"k": sds((1536, page, KVH * words), jnp.int32),
             "v": sds((1536, page, KVH * words), jnp.int32),
             "k_scale": sds((1536, page, KVH), jnp.bfloat16),
             "v_scale": sds((1536, page, KVH), jnp.bfloat16)}

    def body_bytes(chunks):
        plan = plan_lib.KernelPlan(
            op="attention_decode", backend="pallas", interpret=False,
            block_k=chunks * page, chunks=chunks)

        def attend(q, cache, valid, qpos, bt):
            return ulppack_attention.fused_decode_attention(
                q, cache, valid, qpos, kv_bits=4, hd=HD, plan=plan,
                block_tables=bt)

        txt = jax.jit(attend).lower(
            sds((b, 1, H, HD), jnp.bfloat16), cache, sds((b,), jnp.int32),
            sds((b, 1), jnp.int32), sds((b, n_pages), jnp.int32)).as_text()
        body, = re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', txt)
        return len(body)

    small, large = body_bytes(2), body_bytes(8)
    assert large < 1.25 * small, (small, large)
