"""Ahead-of-time kernel planning + backend registry (paper §IV philosophy).

Sparq commits to one execution plan per layer *offline*: pack layout,
shift-extract cadence and accumulator spill distance are all fixed before the
first input arrives (same philosophy as FullPack's ahead-of-time lane layout
planning).  This module is the TPU-side analogue: a ``KernelPlan`` is a frozen,
hashable description of how one op will execute — backend, ``PackSpec``, tile
sizes, and weight-storage mode — built once per layer by a planner that
inspects shapes, the device, and the VMEM budget (DESIGN.md §11).

Three pieces:

  * ``KernelPlan``   — the frozen dataclass.  Hashable, so it can be an
                       ``lru_cache`` key / jit static argument.
  * planners         — ``plan_packed_matmul`` / ``plan_packed_conv2d`` /
                       ``plan_quantize_pack`` / ``plan_int_matmul``.  All are
                       ``lru_cache``d: a layer's plan is built exactly once per
                       process for a given shape signature.
  * backend registry — ``register_backend(op, backend)`` decorates an
                       implementation; ``dispatch(plan, *args)`` routes a call.
                       kernels/ops.py registers 'pallas' and 'xla' entries for
                       every public op and contains no ad-hoc resolution.

Weight-storage modes (``KernelPlan.weight_store``):
  'lanes' — P1-packed lanes (spec.lane_dtype), the default deployed layout.
  'dense' — bit-dense int32 words (true w_bits/value HBM footprint); the
            conv2d Pallas kernel expands words -> P1 lanes in its VMEM
            prologue, the XLA fallback expands at trace level.  ``k_full``
            records the unpacked contraction length (K, or Cin for conv) the
            expansion must recover.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packing import PackSpec
from repro.roofline import hw

#: Fraction of per-core VMEM the planner will budget for one kernel's working
#: set; the rest is headroom for double buffering and compiler temporaries.
VMEM_FRACTION = 0.5

_CONV_BLOCK_H_CANDIDATES = (256, 128, 64, 32, 16, 8, 4, 2, 1)

#: Lane grain of ulppack_matmul's N and K blocks (the TPU vector lane
#: width); K blocks are ``chunks`` of it.
MATMUL_LANES = 128


def default_interpret() -> bool:
    """Pallas kernels run interpreted off-TPU (CPU validation mode).

    This is the default everywhere — the KernelPlan field and every direct
    kernel entry point resolve ``interpret`` from it, so a hand-built plan
    or ad-hoc kernel call on a real TPU compiles instead of silently
    falling into the (orders-of-magnitude slower) Pallas interpreter."""
    return jax.default_backend() != "tpu"


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """Frozen per-layer execution plan; see module docstring.

    Tile fields are populated per-op (``None`` where not applicable):
      packed_matmul              : block_m, block_n, chunks (K block =
                                   chunks x 128 packed lanes)
      int_matmul                 : block_m, block_n, block_k
      packed_conv2d              : block_h, block_co
      quantize_pack              : block_m, block_k
    """

    op: str
    backend: str                      # 'pallas' | 'xla' (never 'auto')
    spec: PackSpec | None = None
    interpret: bool = dataclasses.field(default_factory=default_interpret)
    weight_store: str = "lanes"       # 'lanes' | 'dense'
    k_full: int | None = None         # unpacked K (dense expansion target)
    block_m: int | None = None
    block_n: int | None = None
    block_k: int | None = None
    chunks: int | None = None
    block_h: int | None = None
    block_co: int | None = None
    vmem_bytes: int = 0               # planner working-set estimate
    source: str = "heuristic"         # 'heuristic' | 'tuned' | 'manual'

    def __post_init__(self):
        if self.backend not in ("pallas", "xla"):
            raise ValueError(f"unresolved backend {self.backend!r}")
        if self.weight_store not in ("lanes", "dense"):
            raise ValueError(f"unknown weight_store {self.weight_store!r}")
        if self.weight_store == "dense" and self.k_full is None:
            raise ValueError("dense weight storage requires k_full")
        if self.source not in ("heuristic", "tuned", "manual"):
            raise ValueError(f"unknown plan source {self.source!r}")

    @property
    def vmem_fraction(self) -> float:
        return self.vmem_bytes / hw.VMEM_PER_CORE

    def describe(self) -> dict:
        """Flat report row for benchmarks / the serving engine."""
        d = {"op": self.op, "backend": self.backend,
             "spec": str(self.spec) if self.spec else "",
             "weight_store": self.weight_store,
             "source": self.source,
             "vmem_bytes": self.vmem_bytes,
             "vmem_frac": round(self.vmem_fraction, 4)}
        for f in ("block_m", "block_n", "block_k", "chunks", "block_h",
                  "block_co", "k_full"):
            v = getattr(self, f)
            if v is not None:
                d[f] = v
        return d

    def __str__(self):
        tiles = ",".join(f"{f}={getattr(self, f)}"
                         for f in ("block_m", "block_n", "block_k", "chunks",
                                   "block_h", "block_co")
                         if getattr(self, f) is not None)
        spec = f" {self.spec}" if self.spec else ""
        src = "" if self.source == "heuristic" else f" {self.source}"
        return (f"Plan[{self.op}/{self.backend}{spec} "
                f"store={self.weight_store} {tiles}{src}]")


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------

_BACKENDS: dict[tuple[str, str], object] = {}


def register_backend(op: str, backend: str):
    """Decorator: register ``fn(plan, *args)`` as the (op, backend) impl."""
    def deco(fn):
        _BACKENDS[(op, backend)] = fn
        return fn
    return deco


def get_backend(op: str, backend: str):
    try:
        return _BACKENDS[(op, backend)]
    except KeyError:
        known = sorted(k for k in _BACKENDS if k[0] == op)
        raise KeyError(
            f"no backend {backend!r} registered for op {op!r}; "
            f"registered: {known}") from None


def registered_ops():
    return sorted(_BACKENDS)


def dispatch(plan: KernelPlan, *args, **kwargs):
    """Route a call through the registry according to its plan."""
    return get_backend(plan.op, plan.backend)(plan, *args, **kwargs)


def dispatch_on_mesh(plan: KernelPlan, args: tuple, layout, **kwargs):
    """:func:`dispatch` under the serving mesh, the one place that decides
    how a kernel meets a multi-device mesh.

    GSPMD cannot partition a Mosaic custom call: left to it, every operand
    of a Pallas kernel is gathered whole onto each device (the packed
    weights included).  So while the serving layer has a multi-device mesh
    active (parallel/sharding.activation_mesh), a Pallas plan runs once per
    device in ``jax.shard_map``.  ``layout(mesh)`` returns ``(in_specs,
    out_specs, local_plan)``: the operands' stored layout on the mesh
    (the serving ShardPlan's rules, so no operand is resharded) and the
    plan for one device's shard.  One device, or the 'xla' backend (which
    GSPMD partitions), is plain :func:`dispatch`."""
    from repro.parallel.sharding import kernel_mesh
    mesh = kernel_mesh() if plan.backend == "pallas" else None
    if mesh is None:
        return dispatch(plan, *args, **kwargs)
    in_specs, out_specs, local = layout(mesh)
    return jax.shard_map(
        lambda *a: dispatch(local, *a, **kwargs), mesh=mesh,
        in_specs=in_specs, out_specs=out_specs, check_vma=False)(*args)


def resolve_backend(backend: str = "auto") -> str:
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if backend not in ("pallas", "xla"):
        raise ValueError(f"unknown backend {backend!r}")
    return backend


# ---------------------------------------------------------------------------
# Planners (all lru_cached: one plan per layer signature per process)
# ---------------------------------------------------------------------------

def _lane_bytes(spec: PackSpec) -> int:
    return jnp.dtype(spec.lane_dtype).itemsize


def matmul_working_set(bm: int, bn: int, chunks: int,
                       spec: PackSpec) -> int:
    """ulppack_matmul VMEM accounting for a (bm, bn, bk = chunks * 128)
    step: double-buffered packed lane blocks, their int32 and int8
    unpacked planes, and the s32 accumulator plus double-buffered output
    tile."""
    bk = chunks * MATMUL_LANES
    return (bm * bk + bk * bn) * (2 * _lane_bytes(spec) + 4 + 1) + \
        3 * bm * bn * 4


def matmul_tiles_ok(bm: int, bn: int, chunks: int) -> bool:
    """The TPU compiler's block rule for ulppack_matmul: M blocks in
    multiples of 8 rows, N and K blocks in multiples of 128 lanes."""
    return bm % 8 == 0 and bn % MATMUL_LANES == 0 and chunks >= 1


def conv2d_working_set(block_h: int, block_co: int, *, fh: int, fw: int,
                       w: int, cp: int, cdim: int, out_w: int,
                       spec: PackSpec, weight_store: str) -> int:
    """ulppack_conv2d VMEM accounting: halo-overlapped input tile + weight
    block + s32 accumulator/output tiles (``w`` is the padded input
    width)."""
    lb = _lane_bytes(spec)
    w_bytes = fh * fw * cdim * block_co * \
        (4 if weight_store == "dense" else lb)
    x_tile = (block_h + fh - 1) * w * cp * lb
    acc_out = 2 * block_h * out_w * block_co * 4
    return x_tile + w_bytes + acc_out


def attention_decode_working_set(block_k: int, kvh: int, hd: int,
                                 groups: int) -> int:
    """ulppack_attention per-program VMEM accounting: one KV group's
    unpacked K + V f32 planes, the [KVH, G, block_k] score block, and the
    (m, l, acc) online-softmax carry."""
    return (2 * block_k * kvh * hd * 4 + kvh * groups * block_k * 4
            + kvh * groups * (hd + 2) * 4)


def _tuned_entry(key: str, budget: int, ws_ok) -> dict | None:
    """Consult the active autotune cache; entries whose tiles no longer fit
    the VMEM budget (stale cache, changed budget) are ignored.  ``ws_ok``
    maps an entry to its working-set estimate or None when malformed."""
    from repro.kernels import autotune  # deferred: autotune imports plan

    entry = autotune.lookup(key)
    if entry is None:
        return None
    try:
        ws = ws_ok(entry)
    except (KeyError, TypeError, ValueError):
        return None
    if ws is None or ws > budget:
        return None
    return entry


@functools.lru_cache(maxsize=None)
def plan_packed_matmul(m: int, kp: int, n: int, spec: PackSpec, *,
                       backend: str = "auto", weight_store: str = "lanes",
                       k_full: int | None = None,
                       vmem_budget: int | None = None,
                       use_tuning_cache: bool = True) -> KernelPlan:
    """Plan a packed-lane matmul [m, kp] x [kp, n].

    The autotune cache (kernels/autotune.py) is consulted first: a hit
    whose tiles are TPU-aligned and still fit the VMEM budget becomes the
    plan (``source='tuned'``).  On miss: ``block_m`` covers the rows up to
    128 (a multiple of 8), ``block_n`` is 128 lanes, and the K block is the
    largest ``chunks`` <= 16 (128-lane units) that divides the packed K, so
    the weight operand is never re-padded per call.  Over budget, chunks
    shrink first, then bm.  Every emitted block is a multiple of (8, 128)
    (:func:`matmul_tiles_ok`).
    """
    spec.validate()   # beyond-bound layouts are rejected here, not in-kernel
    backend = resolve_backend(backend)
    if weight_store == "dense" and k_full is None:
        k_full = kp * spec.n_pack
    budget = vmem_budget or int(hw.VMEM_PER_CORE * VMEM_FRACTION)

    def working_set(bm, bn, chunks):
        return matmul_working_set(bm, bn, chunks, spec)

    def tuned_ws(e):
        bm, bn, ch = int(e["block_m"]), int(e["block_n"]), int(e["chunks"])
        return working_set(bm, bn, ch) if matmul_tiles_ok(bm, bn, ch) \
            else None

    if use_tuning_cache:
        from repro.kernels import autotune
        entry = _tuned_entry(
            autotune.matmul_key(m, kp, n, spec, backend=backend,
                                weight_store=weight_store),
            budget, tuned_ws)
        if entry is not None:
            bm, bn, chunks = (int(entry["block_m"]), int(entry["block_n"]),
                              int(entry["chunks"]))
            return KernelPlan(
                op="packed_matmul", backend=backend, spec=spec,
                interpret=default_interpret(), weight_store=weight_store,
                k_full=k_full, block_m=bm, block_n=bn, chunks=chunks,
                vmem_bytes=working_set(bm, bn, chunks),
                source="tuned")

    bm = min(128, -(-m // 8) * 8)
    bn = MATMUL_LANES
    k_blocks = -(-kp // MATMUL_LANES)
    chunks = max(c for c in range(1, 17) if k_blocks % c == 0)
    while chunks > 1 and working_set(bm, bn, chunks) > budget:
        chunks = max(c for c in range(1, chunks) if k_blocks % c == 0)
    while bm > 8 and working_set(bm, bn, chunks) > budget:
        bm = max(8, bm // 2 // 8 * 8)
    return KernelPlan(
        op="packed_matmul", backend=backend, spec=spec,
        interpret=default_interpret(), weight_store=weight_store,
        k_full=k_full, block_m=bm, block_n=bn, chunks=chunks,
        vmem_bytes=working_set(bm, bn, chunks))


@functools.lru_cache(maxsize=None)
def plan_packed_conv2d(x_shape: tuple, w_shape: tuple, spec: PackSpec, *,
                       padding: str = "SAME", backend: str = "auto",
                       weight_store: str = "lanes", k_full: int | None = None,
                       block_h: int | None = None, block_co: int | None = None,
                       vmem_budget: int | None = None,
                       use_tuning_cache: bool = True) -> KernelPlan:
    """Plan a packed conv2d: x [N, H, W, Cp] * w [Fh, Fw, Cdim, Co].

    The autotune cache is consulted first (unless the caller pins tiles with
    ``block_h``/``block_co``): a hit whose tiles fit the VMEM budget becomes
    the plan (``source='tuned'``).  The heuristic fallback picks the largest
    ``block_h`` whose spatially-tiled working set — halo-overlapped input
    tile, weight block, s32 accumulator + output tile — fits the VMEM
    budget, so VMEM use is bounded by the tile rather than the image and
    large resolutions stay feasible (DESIGN.md §10).
    """
    spec.validate()   # beyond-bound layouts are rejected here, not in-kernel
    backend = resolve_backend(backend)
    _, h, w, cp = x_shape
    fh, fw, cdim, co = w_shape
    if weight_store == "dense" and k_full is None:
        k_full = cp * spec.n_pack
    if padding == "SAME":
        h, w = h + fh - 1, w + fw - 1
    out_h, out_w = h - fh + 1, w - fw + 1
    budget = vmem_budget or int(hw.VMEM_PER_CORE * VMEM_FRACTION)

    def working_set_at(bh, bco):
        return conv2d_working_set(bh, bco, fh=fh, fw=fw, w=w, cp=cp,
                                  cdim=cdim, out_w=out_w, spec=spec,
                                  weight_store=weight_store)

    if use_tuning_cache and block_h is None and block_co is None:
        from repro.kernels import autotune
        entry = _tuned_entry(
            autotune.conv2d_key(tuple(x_shape), tuple(w_shape), spec,
                                padding=padding, backend=backend,
                                weight_store=weight_store),
            budget,
            lambda e: working_set_at(int(e["block_h"]), int(e["block_co"])))
        if entry is not None:
            bh = min(int(entry["block_h"]), out_h)
            bco = min(int(entry["block_co"]), co)
            return KernelPlan(
                op="packed_conv2d", backend=backend, spec=spec,
                interpret=default_interpret(), weight_store=weight_store,
                k_full=k_full, block_h=bh, block_co=bco,
                vmem_bytes=working_set_at(bh, bco), source="tuned")

    bco = block_co or min(8, co)

    def working_set(bh):
        return working_set_at(bh, bco)

    if block_h is None:
        if working_set(out_h) <= budget:
            block_h = out_h            # whole image fits: single tile
        else:
            block_h = 1
            for cand in _CONV_BLOCK_H_CANDIDATES:
                if cand < out_h and working_set(cand) <= budget:
                    block_h = cand
                    break
    block_h = min(block_h, out_h)
    return KernelPlan(
        op="packed_conv2d", backend=backend, spec=spec,
        interpret=default_interpret(), weight_store=weight_store,
        k_full=k_full, block_h=block_h, block_co=bco,
        vmem_bytes=working_set(block_h))


@functools.lru_cache(maxsize=None)
def plan_quantize_pack(m: int, k: int, spec: PackSpec, *,
                       backend: str = "auto",
                       vmem_budget: int | None = None) -> KernelPlan:
    """Plan the fused runtime quantize+pack over [m, k] activations."""
    backend = resolve_backend(backend)
    budget = vmem_budget or int(hw.VMEM_PER_CORE * VMEM_FRACTION)
    bm = min(256, -(-m // 8) * 8)
    # cap the K tile at the (n_pack-rounded) activation width: a 512 default
    # on a narrow decode layer would quantize mostly padding
    k_rounded = max(spec.n_pack, -(-k // spec.n_pack) * spec.n_pack)
    bk = min(512, k_rounded)

    def working_set(bm, bk):
        # double-buffered f32 in + s32/s8 lattice + the int8 selection
        # matrix + s32 fields + double-buffered packed lanes + row sums
        kp = bk // spec.n_pack
        return bm * bk * (2 * 4 + 4 + 1) + bk * kp * 5 + \
            bm * kp * (4 + 2 * _lane_bytes(spec)) + bm * 4

    while bm > 8 and working_set(bm, bk) > budget:
        bm //= 2
    return KernelPlan(op="quantize_pack", backend=backend, spec=spec,
                      interpret=default_interpret(), block_m=bm, block_k=bk,
                      vmem_bytes=working_set(bm, bk))


@functools.lru_cache(maxsize=None)
def plan_int_matmul(m: int, k: int, n: int, *, backend: str = "auto",
                    vmem_budget: int | None = None) -> KernelPlan:
    """Plan the unpacked integer matmul baseline."""
    backend = resolve_backend(backend)
    budget = vmem_budget or int(hw.VMEM_PER_CORE * VMEM_FRACTION)
    bm, bn, bk = 128, 128, 512

    def working_set(bm, bn, bk):
        return (bm * bk + bk * bn) * 2 + 2 * bm * bn * 4

    while bk > 64 and working_set(bm, bn, bk) > budget:
        bk //= 2
    return KernelPlan(op="int_matmul", backend=backend, spec=None,
                      interpret=default_interpret(), block_m=bm, block_n=bn,
                      block_k=bk, vmem_bytes=working_set(bm, bn, bk))


@functools.lru_cache(maxsize=None)
def plan_attention_decode(b: int, skv: int, h: int, kvh: int, hd: int,
                          kv_bits: int, *, page_size: int | None = None,
                          backend: str = "auto",
                          vmem_budget: int | None = None,
                          use_tuning_cache: bool = True) -> KernelPlan:
    """Plan the fused flash-decoding attention read (DESIGN.md §20).

    ``skv`` is the logical view length (slot extent, or pages x page_size
    for a paged cache); ``page_size`` non-None selects the paged variant.
    Tile fields: ``block_k`` = KV token rows per online-softmax group,
    ``chunks`` = block-table pages walked per group (paged only; always
    ``block_k // page_size``).  The autotune cache is consulted first
    (kernels/autotune.tune_attention_decode); the heuristic takes 8
    pages' worth of rows (128 for the default 16-row page, contiguous
    caches included), halving while over the VMEM budget — groups only
    amortize the combine epilogue, so smaller is safe.
    """
    backend = resolve_backend(backend)
    groups = max(1, h // kvh)
    budget = vmem_budget or int(hw.VMEM_PER_CORE * VMEM_FRACTION)

    def clamp(bk: int) -> tuple[int, int]:
        """Round a candidate group length to the layout's grain: whole
        pages when paged, <= skv always."""
        if page_size:
            pp = max(1, min(bk // page_size, -(-skv // page_size)))
            return pp * page_size, pp
        return min(max(1, bk), skv), 1

    if use_tuning_cache:
        from repro.kernels import autotune
        entry = _tuned_entry(
            autotune.attention_decode_key(b, skv, h, kvh, hd, kv_bits,
                                          page_size=page_size,
                                          backend=backend),
            budget,
            lambda e: attention_decode_working_set(int(e["block_k"]), kvh,
                                                   hd, groups))
        if entry is not None:
            bk, chunks = clamp(int(entry["block_k"]))
            return KernelPlan(
                op="attention_decode", backend=backend,
                interpret=default_interpret(), block_k=bk, chunks=chunks,
                vmem_bytes=attention_decode_working_set(bk, kvh, hd,
                                                        groups),
                source="tuned")

    # one default group length for both layouts (8 pages of the default
    # 16-row page): the contiguous and the paged read then combine the
    # same token groups, so they agree bit for bit
    bk = 8 * (page_size or 16)
    bk, chunks = clamp(bk)
    while bk > (page_size or 1) and \
            attention_decode_working_set(bk, kvh, hd, groups) > budget:
        bk, chunks = clamp(bk // 2)
    return KernelPlan(
        op="attention_decode", backend=backend,
        interpret=default_interpret(), block_k=bk, chunks=chunks,
        vmem_bytes=attention_decode_working_set(bk, kvh, hd, groups))


def clear_plan_cache():
    """Drop all memoized plans (tests / device changes)."""
    plan_packed_matmul.cache_clear()
    plan_packed_conv2d.cache_clear()
    plan_quantize_pack.cache_clear()
    plan_int_matmul.cache_clear()
    plan_attention_decode.cache_clear()
