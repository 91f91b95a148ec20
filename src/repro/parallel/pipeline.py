"""GPipe-style pipeline parallelism over a mesh axis (the pod axis).

At jamba-398B scale the pod axis can serve as a 2-stage pipeline instead of
extra FSDP: each pod holds half the layers and microbatches flow through a
ppermute ring.  FSDP+TP remains the default on TPU (DESIGN.md §6); this
module provides the PP option and is exercised by tests/test_pipeline.py on
a host mesh with 2 forced devices.

Schedule: classic GPipe fill-drain over T = n_micro + n_stages - 1 ticks.
Stage s computes microbatch m at tick t = s + m; activations hop one stage
per tick via collective_permute.  Bubble fraction = (P-1)/(T) — reported by
``bubble_fraction`` so launch configs can size n_micro.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def gpipe(stage_fn, stage_params, xs, *, mesh, axis: str = "pod"):
    """Run ``xs`` microbatches through a pipeline along ``axis``.

    stage_fn(params, x) -> y: one stage's computation; activation shape is
    preserved across stages (transformer blocks).
    stage_params: pytree whose leaves have a leading stage dim == axis size
    (stage s's slice lives on pod s).
    xs: [n_micro, mb, ...] microbatched inputs (replicated over `axis`).
    Returns [n_micro, mb, ...] outputs.
    """
    n_stages = mesh.shape[axis]
    n_micro = xs.shape[0]
    ticks = n_micro + n_stages - 1
    perm = [(i, i + 1) for i in range(n_stages - 1)]   # stage s -> s+1

    def local(params_s, xs_l):
        stage = jax.lax.axis_index(axis)
        params_s = jax.tree.map(lambda a: a[0], params_s)  # drop stage dim
        buf = jnp.zeros_like(xs_l[0])                      # in-flight act
        outs = jnp.zeros_like(xs_l)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 injects microbatch t (if still filling)
            m_in = jnp.clip(t, 0, n_micro - 1)
            inject = jax.lax.dynamic_index_in_dim(xs_l, m_in, 0,
                                                  keepdims=False)
            x = jnp.where(stage == 0, inject, buf)
            active = (t - stage >= 0) & (t - stage < n_micro)
            y = stage_fn(params_s, x)
            y = jnp.where(active, y, buf)
            # last stage collects microbatch t - (P-1)
            m_out = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            collect = (stage == n_stages - 1) & active
            outs = jax.lax.cond(
                collect,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, m_out, 0),
                lambda o: o, outs)
            # hop activations one stage forward
            buf = jax.lax.ppermute(y, axis, perm)
            return buf, outs

        _, outs = jax.lax.fori_loop(0, ticks, tick, (buf, outs))
        # outputs live on the last stage: broadcast to all stages
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P(*([None] * xs.ndim))),
        out_specs=P(*([None] * xs.ndim)),
        check_vma=False)(stage_params, xs)
