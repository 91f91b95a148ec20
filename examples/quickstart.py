"""Quickstart: the paper's technique end-to-end in 60 seconds.

1. Build a sub-byte packed linear layer (W2A2, int16 lanes).
2. Validate the packed integer path against the float oracle.
3. Run the Pallas packed matmul (compiled on a TPU, interpreted on the
   CPU) and check exactness.
4. Show the overflow-free region (paper Fig. 5 boundary).

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing
from repro.core.packing import PackSpec, overflow_free_region
from repro.kernels import ops, ref
from repro.kernels.plan import default_interpret
from repro.kernels.ulppack_matmul import ulppack_matmul

rng = np.random.default_rng(0)

# --- 1. a quantized linear: offline weight packing, runtime act packing ---
spec = PackSpec(w_bits=2, a_bits=2, lane_dtype=jnp.int16.dtype)
print(f"packing spec: {spec}  (k_tile={spec.k_tile} packed lanes between "
      "extractions)")

x = jnp.asarray(rng.normal(size=(4, 256)), jnp.float32)
w = jnp.asarray(rng.normal(size=(256, 64)) * 0.1, jnp.float32)
w_scale, w_zp = jnp.float32(0.02), jnp.int32(2)
a_scale, a_zp = jnp.float32(0.08), jnp.int32(2)

w_packed, col_sums = ops.prepare_weights(w, w_scale, w_zp, spec)
print(f"weights: {w.shape} f32 -> packed lanes {w_packed.shape} "
      f"{w_packed.dtype} ({w_packed.size * 2} bytes vs {w.size * 4})")

y = ops.quantized_linear(x, w_packed, col_sums, a_scale, a_zp, w_scale,
                         w_zp, spec, backend="xla")
y_ref = ref.quantized_linear_ref(x, w, a_scale, a_zp, w_scale, w_zp,
                                 spec.a_bits, spec.w_bits)
print("packed vs float-oracle max err:",
      float(jnp.max(jnp.abs(y - y_ref))))

# --- 2. the Pallas packed matmul: compiled on a TPU, interpreted elsewhere
interpret = default_interpret()
q_a = jnp.asarray(rng.integers(0, 4, (8, 200)), jnp.int32)
q_w = jnp.asarray(rng.integers(0, 4, (200, 16)), jnp.int32)
ap = packing.pack_activations(q_a, spec, -1)
wp = packing.pack_weights(q_w, spec, 0)
got = ulppack_matmul(ap, wp, spec, block_m=8, block_n=128, chunks=1,
                     interpret=interpret)
want = ref.matmul_i32_ref(q_a, q_w)
assert jnp.array_equal(got, want), "kernel mismatch!"
print(f"Pallas ulppack_matmul ({'interpreted' if interpret else 'compiled'}"
      f"): EXACT match with integer oracle")

# --- 3. the overflow-free region (paper Fig. 5 / N+M<=7) ---
print("\noverflow-free k_tile table, int16 lanes (0 = unusable):")
region = overflow_free_region(jnp.int16.dtype, max_bits=4)
print("      A=1  A=2  A=3  A=4")
for wb in range(1, 5):
    row = [f"{region[(wb, ab)]:4d}" for ab in range(1, 5)]
    print(f"W={wb} " + " ".join(row))
print("(reproduces the paper's N+M<=7 boundary: W4A4 is 0)")
