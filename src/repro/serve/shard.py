"""Serving ShardPlan: explicit tensor-parallel layout for packed inference.

The serving stack was implicitly single-device: ``prepare_serving_params``
packed weights on whatever device jax defaulted to, ``ServingEngine`` jitted
its steps against unsharded trees, and "how is this tensor laid out across
devices" lived nowhere.  A :class:`ShardPlan` makes that an explicit object
(DESIGN.md §15): given a serving mesh it computes NamedShardings for every
leaf of the *packed* serving tree and for the (possibly sub-byte packed)
decode caches, and the engine places both before jitting.

Layout scheme — chosen so sub-byte packing stays exact under sharding:

* **Packed weights shard the output (N) axis** over the TP axis
  ('model'), i.e. every packed Dense is column-parallel.  Lane packing
  (P1) and bit-dense word packing both run along the *contraction* (K)
  axis, which this scheme keeps replicated — so an int32 word or int16
  lane never straddles a shard boundary and each device holds whole,
  locally-decodable words ("packing along the replicated axis",
  ISSUE 5).  Row-parallel K-sharding would make XLA psum *packed* s32
  totals across shards before shift-mask extraction — summing more than
  ``k_tile`` lanes' worth of D-band contributions, which overflows the
  field and silently corrupts the dot (core/packing.k_tile_bound).
* ``col_sums`` / ``bias`` ([N]) shard with their columns; quant scalars
  (``w_scale``/``a_scale``/``w_zp``/``a_zp``) replicate.
* **Unpacked leaves replicate** (embedding tables, norms, fake-quant MoE
  experts): serving batches are small, replication keeps the gather /
  einsum paths collective-free.  The sharded-vocab embedding lookup in
  models/common still engages under the active mesh (shard_map + psum of
  masked gathers — exact, each row is one shard's value plus zeros).
* **KV caches shard the kv-head axis** (axis 2 of [B, S, KVH, hd|words]
  and of the [B, S, KVH] scale planes) over 'model' — quantization,
  word-packing, ring writes and fused-dequant reads are all per-(pos,
  kv-head) local, so a head shard never touches another shard's words
  (parallel/sharding.cache_shardings(kv_head_shard=True)).  A page
  pool's lane-flat value leaves [P, ps, KVH * w] shard their last axis
  instead, in whole heads where 'model' divides KVH.  Recurrent
  states (mamba conv/ssm, xLSTM C/n/m) shard their channel dims via the
  same rules.

Every rule is divisibility-guarded: an axis that does not divide the dim is
dropped (replicated), so a mesh with model=1 — or a tensor that cannot
shard — degrades to exactly the single-device layout and the engine is
behaviorally unchanged.
"""

from __future__ import annotations

import dataclasses
import re

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.parallel import sharding as sharding_lib

#: Packed-Dense leaf names whose trailing axis is the output (N) axis.
_COLUMN_LEAVES = re.compile(r"/(w_packed|w_dense|kernel)$")
_VECTOR_LEAVES = re.compile(r"/(col_sums|bias)$")
_SCALAR_LEAVES = re.compile(r"/(w_scale|a_scale|w_zp|a_zp|k_full|w_step|"
                            r"a_step)$")


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """Frozen description of how one serving deployment lays tensors out.

    ``axis`` is the tensor-parallel mesh axis name.  The plan is pure
    metadata — building one never touches device state; placement happens
    in :meth:`place_params` / :meth:`place_caches` (device_put with the
    computed NamedShardings), which the engine calls once at init.
    """

    mesh: Mesh
    axis: str = "model"

    @property
    def model_shards(self) -> int:
        return int(self.mesh.shape.get(self.axis, 1))

    def shards_of(self, n: int) -> int:
        """How many ways dim ``n`` actually shards (1 when indivisible)."""
        s = self.model_shards
        return s if s > 0 and n % s == 0 else 1

    def local_out(self, n: int) -> int:
        """Per-shard local size of an output dim planned at global ``n``.

        This is the shape serve/prepare.build_layer_plans plans against:
        KernelPlan signatures — and therefore the PR 4 autotune cache keys
        — describe what one shard executes, not the global matmul.
        """
        return n // self.shards_of(n)

    # ------------------------------------------------------------------
    # Param shardings (packed serving tree)
    # ------------------------------------------------------------------

    def param_pspec(self, path: str, leaf) -> P:
        shape = np.shape(leaf)
        if not shape or _SCALAR_LEAVES.search(path):
            return P()
        if _VECTOR_LEAVES.search(path) and len(shape) == 1:
            return self._guard(shape, P(self.axis))
        if _COLUMN_LEAVES.search(path) and len(shape) == 2:
            # [Kp|Kw|K, N]: shard columns; K (where the packed words /
            # lanes live) stays replicated => word boundaries shard-local
            return self._guard(shape, P(None, self.axis))
        return P(*([None] * len(shape)))       # replicate everything else

    def param_shardings(self, params):
        def one(path, leaf):
            ps = sharding_lib.path_str(path)
            return NamedSharding(self.mesh, self.param_pspec(f"/{ps}", leaf))
        return jax.tree_util.tree_map_with_path(one, params)

    def place_params(self, params):
        """device_put the packed tree onto the mesh per the plan."""
        return jax.device_put(params, self.param_shardings(params))

    # ------------------------------------------------------------------
    # Cache shardings (kv-head axis; quantized layouts included)
    # ------------------------------------------------------------------

    def cache_shardings(self, caches, cfg, batch: int, *,
                        paged: bool = False):
        """``paged=True``: the attention leaves are page pools, values
        lane-flat [P, page_size, KVH * w] and scale planes
        [P, page_size, KVH] — the kv-head shard becomes a shard of the
        lane axis, head-local where the axis divides KVH (else the leaf
        replicates), and the page axis replicates: pages are shared
        physical capacity any slot's block table may point into
        (DESIGN.md §18)."""
        return sharding_lib.cache_shardings(
            caches, cfg, self.mesh, batch, kv_head_shard=True, paged=paged)

    def place_caches(self, caches, cfg, batch: int, *, paged: bool = False):
        shardings = self.cache_shardings(caches, cfg, batch, paged=paged)
        return jax.tree.map(
            lambda c, s: None if c is None else jax.device_put(c, s),
            caches, shardings, is_leaf=lambda x: x is None)

    # ------------------------------------------------------------------

    def _guard(self, shape, spec: P) -> P:
        return sharding_lib._guard(self.mesh, shape, spec)

    def describe(self) -> dict:
        """Flat report row (serve CLI / microbench)."""
        return {"mesh": dict(self.mesh.shape), "tp_axis": self.axis,
                "model_shards": self.model_shards}
