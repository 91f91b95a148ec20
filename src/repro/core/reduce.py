"""Float sums whose rounding does not depend on the program around them.

XLA chooses a reduction's association order per compiled program, from the
shapes and fusions around it.  On a TPU v5e the same [2048]-wide row sum
rounds differently inside a 32-row prefill window and inside a 544-row
uncached window, so a served token could depend on how a request was
chunked or what it was batched with.  Elementwise adds are never
reassociated, so a pairwise tree of them rounds the same way in every
program (DESIGN.md §21).
"""

from __future__ import annotations

import jax.numpy as jnp


def ordered_sum(x, axis: int = -1, keepdims: bool = False):
    """Sum of ``x`` along ``axis`` in one fixed pairwise order: the axis is
    zero-padded to a power of two, then halved by elementwise adds
    (``x[:n/2] + x[n/2:]``) until one element is left."""
    x = jnp.moveaxis(x, axis, -1)
    n = x.shape[-1]
    width = 1 << max(0, n - 1).bit_length()
    if width != n:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - n)])
    while width > 1:
        width //= 2
        x = x[..., :width] + x[..., width:]
    if keepdims:
        return jnp.moveaxis(x, -1, axis)
    return x[..., 0]
