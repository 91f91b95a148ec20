"""Empirical KernelPlan autotuner with a persisted, schema-versioned cache.

The static planners in kernels/plan.py pick tile sizes from closed-form VMEM
accounting — correct, but shape-agnostic beyond the budget test.  Sparq's
speedups (3.2x at 2-bit, 1.7x at 4-bit over int16) come from matching the
schedule to the hardware's vector geometry per shape, and FullPack makes the
same point for lane layout: sub-byte throughput is won or lost in per-shape
tile selection.  This module is the software analogue — an offline
measurement pass over a *bounded* candidate grid:

  * ``tune_packed_matmul``   — block_m / block_n / chunks
  * ``tune_packed_conv2d``   — block_h / block_co
  * ``tune_attention_chunk`` — q-chunk of the fused-dequant attention loop
  * ``tune_matmul_layout`` / ``tune_conv2d_layout`` — the PackSpec lane
    layout itself (packing.LAYOUT_FAMILY), tiling each candidate via the
    tuners above and verifying bit-exactness vs the unpacked reference

Layout choices are keyed WITHOUT the row count (weights pack once offline
and serve every batch size) and resolved by ``matmul_layout_for`` /
``conv2d_layout_for`` — the one function packers, planners, and dispatch all
call, so the layout the stored bytes use and the layout the kernel expects
can never drift while one cache is active (DESIGN.md §16).

Winners are persisted to a JSON tuning cache (``reports/autotune_<device>.
json``; the CPU cache is committed so CI plans deterministically).  The
planners consult the *active* cache first and fall back to their heuristics
on miss; plans stay frozen/``lru_cache``d, so dispatch cost is unchanged
(DESIGN.md §14).

Cache discipline:
  * schema-versioned — a stale or corrupt file is ignored with a warning,
    never an error (the heuristics always work);
  * keyed by kernel signature: op kind, shapes, PackSpec, weight storage,
    backend — and scoped to one device kind per file;
  * entries record the winner's tiles plus measured ``wall_us`` and the
    heuristic's ``heuristic_us`` so benchmarks can report tuned-vs-heuristic
    without re-measuring.

``measure_us`` is the shared timing primitive (median-of-repeats with a
minimum total measurement time); benchmarks/common.py delegates to it so the
CI perf-regression gate and the tuner agree on methodology.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import time
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing
from repro.core.packing import PackSpec
from repro.kernels import plan as plan_lib
from repro.roofline import hw

# Schema 2: PackSpec key strings grew an explicit shift suffix
# ("W2A2/int16xP2s8") and the cache gained layout_* entries recording the
# winning lane layout per shape.  Schema-1 files are ignored with a warning
# and the planners fall back to heuristics (no migration needed — re-tune).
SCHEMA_VERSION = 2

#: Environment override for the cache file the active cache loads from.
ENV_CACHE = "REPRO_AUTOTUNE_CACHE"

#: Candidate grids (bounded by construction; the budget filter shrinks them
#: further per shape).
MATMUL_BLOCK_M = (8, 16, 32, 64, 128, 256)
MATMUL_BLOCK_N = (128, 256)
MATMUL_CHUNKS = (1, 2, 4, 8, 16)
CONV_BLOCK_CO = (4, 8, 16, 32)
ATTN_CHUNKS = (32, 64, 128, 256, 512)
#: KV token rows per online-softmax group of the fused decode kernel
#: (DESIGN.md §20); paged shapes round each candidate to whole pages.
ATTN_DECODE_SPLITS = (64, 128, 256, 512, 1024)

_REPO_ROOT = Path(__file__).resolve().parents[3]


def device_kind() -> str:
    """The device axis of the cache key space ('cpu' / 'tpu' / 'gpu')."""
    return jax.default_backend()


def default_cache_path(device: str | None = None) -> str:
    """$REPRO_AUTOTUNE_CACHE if set, else reports/autotune_<device>.json
    at the repo root (so tests and benchmarks agree regardless of CWD)."""
    env = os.environ.get(ENV_CACHE)
    if env:
        return env
    return str(_REPO_ROOT / "reports"
               / f"autotune_{device or device_kind()}.json")


# ---------------------------------------------------------------------------
# Cache keys — human-readable, deterministic strings
# ---------------------------------------------------------------------------

def matmul_key(m: int, kp: int, n: int, spec: PackSpec, *, backend: str,
               weight_store: str = "lanes") -> str:
    return (f"packed_matmul|{backend}|m={m}|kp={kp}|n={n}|spec={spec}"
            f"|store={weight_store}")


def conv2d_key(x_shape: tuple, w_shape: tuple, spec: PackSpec, *,
               padding: str, backend: str,
               weight_store: str = "lanes") -> str:
    xs = "x".join(str(d) for d in x_shape)
    ws = "x".join(str(d) for d in w_shape)
    return (f"packed_conv2d|{backend}|x={xs}|w={ws}|pad={padding}"
            f"|spec={spec}|store={weight_store}")


def attention_key(b: int, sq: int, skv: int, h: int, kvh: int, hd: int,
                  kv_bits: int) -> str:
    return (f"attention_chunk|b={b}|sq={sq}|skv={skv}|h={h}|kvh={kvh}"
            f"|hd={hd}|kv_bits={kv_bits}")


def attention_decode_key(b: int, skv: int, h: int, kvh: int, hd: int,
                         kv_bits: int, *, page_size: int | None,
                         backend: str) -> str:
    paged = f"|ps={page_size}" if page_size else ""
    return (f"attention_decode|{backend}|b={b}|skv={skv}|h={h}|kvh={kvh}"
            f"|hd={hd}|kv_bits={kv_bits}{paged}")


def matmul_layout_key(k: int, n: int, w_bits: int, a_bits: int, *,
                      backend: str, weight_store: str = "lanes") -> str:
    """Lane-layout choice for a [*, k] x [k, n] weight.  Deliberately NOT
    keyed on the row count: weights are packed once offline and serve every
    batch size, so one layout must win across m."""
    return (f"layout_matmul|{backend}|k={k}|n={n}|w={w_bits}|a={a_bits}"
            f"|store={weight_store}")


def conv2d_layout_key(x_shape: tuple, w_shape: tuple, w_bits: int,
                      a_bits: int, *, padding: str, backend: str,
                      weight_store: str = "lanes") -> str:
    """Lane-layout choice for a conv2d; shapes are the UNPACKED
    x [N, H, W, Cin] and w [Fh, Fw, Cin, Co] (layout-independent)."""
    xs = "x".join(str(d) for d in x_shape)
    ws = "x".join(str(d) for d in w_shape)
    return (f"layout_conv2d|{backend}|x={xs}|w={ws}|pad={padding}"
            f"|wb={w_bits}|ab={a_bits}|store={weight_store}")


# ---------------------------------------------------------------------------
# TuningCache: load / lookup / store / save
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TuningCache:
    """One device's tuning results: {signature key: winner entry}."""

    device: str
    entries: dict = dataclasses.field(default_factory=dict)
    path: str | None = None

    def lookup(self, key: str) -> dict | None:
        return self.entries.get(key)

    def store(self, key: str, entry: dict) -> None:
        self.entries[key] = entry

    def to_json(self) -> dict:
        return {"schema": SCHEMA_VERSION, "device": self.device,
                "entries": self.entries}

    def save(self, path: str | None = None) -> str:
        path = path or self.path or default_cache_path(self.device)
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=2, sort_keys=True)
            f.write("\n")
        self.path = path
        return path

    @classmethod
    def load(cls, path: str) -> "TuningCache | None":
        """Parse a cache file; corrupt or stale-schema files are ignored
        with a warning (the planner heuristics remain the fallback)."""
        if not os.path.exists(path):
            return None
        try:
            with open(path) as f:
                raw = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            warnings.warn(f"ignoring corrupt autotune cache {path}: {e}",
                          stacklevel=2)
            return None
        if not isinstance(raw, dict) or raw.get("schema") != SCHEMA_VERSION:
            warnings.warn(
                f"ignoring autotune cache {path}: schema "
                f"{raw.get('schema') if isinstance(raw, dict) else '?'} != "
                f"{SCHEMA_VERSION}", stacklevel=2)
            return None
        entries = raw.get("entries")
        if not isinstance(entries, dict):
            warnings.warn(f"ignoring autotune cache {path}: no entries dict",
                          stacklevel=2)
            return None
        return cls(device=raw.get("device", "unknown"), entries=entries,
                   path=path)


# ---------------------------------------------------------------------------
# Active cache (what the planners consult)
# ---------------------------------------------------------------------------

_UNSET = object()
_active: TuningCache | object | None = _UNSET


def active_cache() -> TuningCache:
    """The process-wide cache the planners consult.  Lazily loaded from
    ``default_cache_path()`` on first use; an empty per-device cache when
    no file exists (every lookup then misses -> heuristics)."""
    global _active
    if _active is _UNSET:
        dev = device_kind()
        _active = (TuningCache.load(default_cache_path(dev))
                   or TuningCache(device=dev))
    return _active


def set_active_cache(cache: TuningCache) -> TuningCache:
    """Install a cache and invalidate every memoized plan built under the
    previous one (plans are frozen per process otherwise)."""
    global _active
    _active = cache
    plan_lib.clear_plan_cache()
    attention_chunk_for.cache_clear()
    return cache


def load_cache(path: str) -> TuningCache:
    """Load + activate ``path`` (empty active cache if unreadable)."""
    return set_active_cache(TuningCache.load(path)
                            or TuningCache(device=device_kind()))


def reset_active_cache() -> None:
    """Back to the lazy default (tests; device changes)."""
    global _active
    _active = _UNSET
    plan_lib.clear_plan_cache()
    attention_chunk_for.cache_clear()


def lookup(key: str) -> dict | None:
    """Planner-facing lookup against the active cache (never raises)."""
    try:
        return active_cache().lookup(key)
    except Exception as e:  # a broken cache must never break planning
        warnings.warn(f"autotune lookup failed: {e}", stacklevel=2)
        return None


def _store(cache: TuningCache, key: str, entry: dict) -> None:
    """Store a tuning result; writes to the ACTIVE cache invalidate every
    memoized plan so later planner calls see the new entry."""
    cache.store(key, entry)
    if cache is _active:
        plan_lib.clear_plan_cache()
        attention_chunk_for.cache_clear()


# ---------------------------------------------------------------------------
# Timing: median-of-repeats with a minimum total measurement time
# ---------------------------------------------------------------------------

def measure_us(fn, *args, repeats: int = 3, min_time_s: float = 0.01,
               iters: int = 1, max_calls: int = 256,
               warmup: int = 1) -> float:
    """Median-of-``repeats`` wall time per call, in microseconds.

    Each sample times a batch of calls; the batch size starts at ``iters``
    and doubles until one batch takes at least ``min_time_s`` (capped at
    ``max_calls``), so fast kernels are not measured at timer resolution and
    the CI regression gate does not flake on noisy runners.  The first
    (timed) calibration batch also absorbs any remaining compilation."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))

    def batch(ncalls: int) -> float:
        t0 = time.perf_counter()
        for _ in range(ncalls):
            jax.block_until_ready(fn(*args))
        return time.perf_counter() - t0

    n = max(1, int(iters))
    dt = batch(n)
    while dt < min_time_s and n < max_calls:
        n = min(n * 2, max_calls)
        dt = batch(n)
    samples = [dt / n]
    for _ in range(max(0, repeats - 1)):
        samples.append(batch(n) / n)
    return float(np.median(samples) * 1e6)


# ---------------------------------------------------------------------------
# Candidate grids
# ---------------------------------------------------------------------------

def _pow2_cap(grid, dim: int):
    """Drop grid points whose predecessor already covers ``dim`` (a block
    twice the problem size only adds padding, never a new schedule)."""
    out = []
    for g in grid:
        out.append(g)
        if g >= dim:
            break
    return out


def _bound(cands: list, limit: int) -> list:
    """Deterministically subsample an over-long candidate list."""
    if len(cands) <= limit:
        return cands
    step = len(cands) / limit
    return [cands[int(i * step)] for i in range(limit)]


def matmul_candidates(m: int, kp: int, n: int, spec: PackSpec,
                      budget: int, *, limit: int = 16) -> list[tuple]:
    """(block_m, block_n, chunks) triples under the VMEM budget."""
    cands = []
    for bm in _pow2_cap(MATMUL_BLOCK_M, m):
        for bn in _pow2_cap(MATMUL_BLOCK_N, n):
            for ch in MATMUL_CHUNKS:
                if (ch - 1) * plan_lib.MATMUL_LANES >= kp:
                    break   # K block past the packed K: padding only
                if plan_lib.matmul_working_set(bm, bn, ch, spec) <= budget:
                    cands.append((bm, bn, ch))
    return _bound(cands, limit)


def conv2d_candidates(out_h: int, co: int, ws_fn, budget: int, *,
                      limit: int = 12) -> list[tuple]:
    """(block_h, block_co) pairs under the VMEM budget; ``ws_fn(bh, bco)``
    is the planner's working-set estimate for the shape being tuned."""
    bhs = sorted({min(b, out_h)
                  for b in plan_lib._CONV_BLOCK_H_CANDIDATES + (out_h,)})
    bcos = sorted({min(b, co) for b in CONV_BLOCK_CO})
    cands = [(bh, bco) for bh in bhs for bco in bcos
             if ws_fn(bh, bco) <= budget]
    return _bound(cands, limit)


# ---------------------------------------------------------------------------
# Tuners (offline: measure candidates, persist the winner)
# ---------------------------------------------------------------------------

def _entry(best: tuple, heuristic_us: float, n_cands: int,
           **tiles) -> dict:
    wall, vmem = best
    e = dict(tiles)
    e.update({"wall_us": round(wall, 2),
              "heuristic_us": round(heuristic_us, 2),
              "vmem_bytes": int(vmem), "candidates": n_cands})
    return e


def tune_packed_matmul(m: int, kp: int, n: int, spec: PackSpec, *,
                       backend: str = "auto", weight_store: str = "lanes",
                       k_full: int | None = None,
                       vmem_budget: int | None = None,
                       cache: TuningCache | None = None,
                       max_candidates: int = 16, repeats: int = 3,
                       force: bool = False, seed: int = 0) -> dict:
    """Benchmark the (block_m, block_n, chunks) grid for one matmul
    signature and store the winner in ``cache`` (active cache default)."""
    from repro.kernels import ops  # registers the backends

    backend = plan_lib.resolve_backend(backend)
    cache = cache if cache is not None else active_cache()
    if weight_store == "dense" and k_full is None:
        k_full = kp * spec.n_pack
    key = matmul_key(m, kp, n, spec, backend=backend,
                     weight_store=weight_store)
    if not force:
        hit = cache.lookup(key)
        if hit is not None:
            return hit
    budget = vmem_budget or int(hw.VMEM_PER_CORE * plan_lib.VMEM_FRACTION)
    heur = plan_lib.plan_packed_matmul(
        m, kp, n, spec, backend=backend, weight_store=weight_store,
        k_full=k_full, vmem_budget=vmem_budget, use_tuning_cache=False)

    rng = np.random.default_rng(seed)
    k = k_full if k_full is not None else kp * spec.n_pack
    q_a = jnp.asarray(rng.integers(0, spec.max_a + 1, (m, k)), jnp.int32)
    q_w = jnp.asarray(rng.integers(0, spec.max_w + 1, (k, n)), jnp.int32)
    ap = packing.pack_activations(q_a, spec, axis=-1)
    if weight_store == "dense":
        wp = ops.dense_store_weights(q_w, spec.w_bits)
    else:
        wp = packing.pack_weights(q_w, spec, axis=0)

    cands = matmul_candidates(m, kp, n, spec, budget, limit=max_candidates)
    heur_tiles = (heur.block_m, heur.block_n, heur.chunks)
    if heur_tiles not in cands:
        cands.append(heur_tiles)

    best, heuristic_us = None, None
    for bm, bn, ch in cands:
        ws = plan_lib.matmul_working_set(bm, bn, ch, spec)
        plan = dataclasses.replace(heur, block_m=bm, block_n=bn, chunks=ch,
                                   vmem_bytes=ws, source="tuned")
        us = measure_us(lambda: plan_lib.dispatch(plan, ap, wp),
                        repeats=repeats)
        if (bm, bn, ch) == heur_tiles:
            heuristic_us = us
        if best is None or us < best[0]:
            best = (us, ws, bm, bn, ch)

    us, ws, bm, bn, ch = best
    entry = _entry((us, ws), heuristic_us, len(cands),
                   block_m=bm, block_n=bn, chunks=ch)
    _store(cache, key, entry)
    return entry


def tune_packed_conv2d(x_shape: tuple, w_shape: tuple, spec: PackSpec, *,
                       padding: str = "SAME", backend: str = "auto",
                       weight_store: str = "lanes",
                       k_full: int | None = None,
                       vmem_budget: int | None = None,
                       cache: TuningCache | None = None,
                       max_candidates: int = 12, repeats: int = 3,
                       force: bool = False, seed: int = 0) -> dict:
    """Benchmark the (block_h, block_co) grid for one conv2d signature."""
    from repro.kernels import ops

    backend = plan_lib.resolve_backend(backend)
    cache = cache if cache is not None else active_cache()
    nb, h, w, cp = x_shape
    fh, fw, cdim, co = w_shape
    if weight_store == "dense" and k_full is None:
        k_full = cp * spec.n_pack
    key = conv2d_key(tuple(x_shape), tuple(w_shape), spec, padding=padding,
                     backend=backend, weight_store=weight_store)
    if not force:
        hit = cache.lookup(key)
        if hit is not None:
            return hit
    budget = vmem_budget or int(hw.VMEM_PER_CORE * plan_lib.VMEM_FRACTION)
    heur = plan_lib.plan_packed_conv2d(
        tuple(x_shape), tuple(w_shape), spec, padding=padding,
        backend=backend, weight_store=weight_store, k_full=k_full,
        vmem_budget=vmem_budget, use_tuning_cache=False)

    rng = np.random.default_rng(seed)
    cin = k_full if k_full is not None else cp * spec.n_pack
    q_x = jnp.asarray(rng.integers(0, spec.max_a + 1, (nb, h, w, cin)),
                      jnp.int32)
    q_w = jnp.asarray(rng.integers(0, spec.max_w + 1, (fh, fw, cin, co)),
                      jnp.int32)
    xp = packing.pack_activations(q_x, spec, axis=-1)
    if weight_store == "dense":
        wp = ops.dense_store_conv_weights(q_w, spec.w_bits)
    else:
        wp = packing.pack_weights(q_w, spec, axis=2)

    ph, pw = (h + fh - 1, w + fw - 1) if padding == "SAME" else (h, w)
    out_h, out_w = ph - fh + 1, pw - fw + 1

    def ws_fn(bh, bco):
        return plan_lib.conv2d_working_set(
            bh, bco, fh=fh, fw=fw, w=pw, cp=cp, cdim=cdim, out_w=out_w,
            spec=spec, weight_store=weight_store)

    cands = conv2d_candidates(out_h, co, ws_fn, budget,
                              limit=max_candidates)
    heur_tiles = (heur.block_h, heur.block_co)
    if heur_tiles not in cands:
        cands.append(heur_tiles)

    best, heuristic_us = None, None
    for bh, bco in cands:
        ws = ws_fn(bh, bco)
        plan = dataclasses.replace(heur, block_h=bh, block_co=bco,
                                   vmem_bytes=ws, source="tuned")
        us = measure_us(lambda: plan_lib.dispatch(plan, xp, wp, padding),
                        repeats=repeats)
        if (bh, bco) == heur_tiles:
            heuristic_us = us
        if best is None or us < best[0]:
            best = (us, ws, bh, bco)

    us, ws, bh, bco = best
    entry = _entry((us, ws), heuristic_us, len(cands),
                   block_h=bh, block_co=bco)
    _store(cache, key, entry)
    return entry


# ---------------------------------------------------------------------------
# Lane-layout sweep: PackSpec as a tuning axis (FullPack-style selection)
# ---------------------------------------------------------------------------

def tune_matmul_layout(m: int, k: int, n: int, base_spec: PackSpec, *,
                       backend: str = "auto", weight_store: str = "lanes",
                       vmem_budget: int | None = None,
                       cache: TuningCache | None = None,
                       max_candidates: int = 16, repeats: int = 3,
                       force: bool = False, seed: int = 0) -> dict:
    """Sweep packing.LAYOUT_FAMILY for one [m, k] x [k, n] matmul.

    Each candidate layout is tile-tuned via :func:`tune_packed_matmul` (so
    the winning layout also lands with tuned tiles) and verified bit-exact
    against the unpacked integer reference before it may win; a layout that
    ever mismatched would silently corrupt every layer packed under it.
    The winner is recorded under :func:`matmul_layout_key` — keyed on
    (k, n), not m — and resolved by :func:`matmul_layout_for`.
    """
    from repro.kernels import ops, ref  # registers the backends

    backend = plan_lib.resolve_backend(backend)
    cache = cache if cache is not None else active_cache()
    key = matmul_layout_key(k, n, base_spec.w_bits, base_spec.a_bits,
                            backend=backend, weight_store=weight_store)
    if not force:
        hit = cache.lookup(key)
        if hit is not None:
            return hit

    rng = np.random.default_rng(seed)
    q_a = jnp.asarray(rng.integers(0, base_spec.max_a + 1, (m, k)),
                      jnp.int32)
    q_w = jnp.asarray(rng.integers(0, base_spec.max_w + 1, (k, n)),
                      jnp.int32)
    want = np.asarray(ref.matmul_i32_ref(q_a, q_w))

    best, base_us, tried = None, None, 0
    for spec in packing.layout_family(base_spec.w_bits, base_spec.a_bits,
                                      base_spec):
        kp = -(-k // spec.n_pack)
        k_full = k if weight_store == "dense" else None
        entry = tune_packed_matmul(
            m, kp, n, spec, backend=backend, weight_store=weight_store,
            k_full=k_full, vmem_budget=vmem_budget, cache=cache,
            max_candidates=max_candidates, repeats=repeats, force=force,
            seed=seed)
        # Mandatory: the layout must reproduce the unpacked reference
        # bit-for-bit through the tuned plan before it can be selected.
        ap = packing.pack_activations(q_a, spec, axis=-1)
        if weight_store == "dense":
            wp = ops.dense_store_weights(q_w, spec.w_bits)
        else:
            wp = packing.pack_weights(q_w, spec, axis=0)
        got = np.asarray(ops.packed_matmul(
            ap, wp, spec, backend=backend, weight_store=weight_store,
            k_full=k_full))
        if not np.array_equal(got, want):
            warnings.warn(f"layout candidate {spec} failed bit-exactness "
                          f"at m={m} k={k} n={n}; excluded", stacklevel=2)
            continue
        tried += 1
        us = float(entry["wall_us"])
        if spec == base_spec:
            base_us = us
        if best is None or us < best[0]:
            best = (us, spec)

    us, spec = best
    layout_entry = {"spec": str(spec), "wall_us": round(us, 2),
                    "base_spec": str(base_spec),
                    "base_us": (round(base_us, 2) if base_us is not None
                                else None),
                    "candidates": tried}
    _store(cache, key, layout_entry)
    return layout_entry


def tune_conv2d_layout(x_shape: tuple, w_shape: tuple,
                       base_spec: PackSpec, *, padding: str = "SAME",
                       backend: str = "auto", weight_store: str = "lanes",
                       vmem_budget: int | None = None,
                       cache: TuningCache | None = None,
                       max_candidates: int = 12, repeats: int = 3,
                       force: bool = False, seed: int = 0) -> dict:
    """Layout sweep for one conv2d; ``x_shape``/``w_shape`` are the UNPACKED
    x [N, H, W, Cin] and w [Fh, Fw, Cin, Co] (see tune_matmul_layout)."""
    from repro.kernels import ops, ref

    backend = plan_lib.resolve_backend(backend)
    cache = cache if cache is not None else active_cache()
    nb, h, w, cin = x_shape
    fh, fw, _, co = w_shape
    key = conv2d_layout_key(tuple(x_shape), tuple(w_shape),
                            base_spec.w_bits, base_spec.a_bits,
                            padding=padding, backend=backend,
                            weight_store=weight_store)
    if not force:
        hit = cache.lookup(key)
        if hit is not None:
            return hit

    rng = np.random.default_rng(seed)
    q_x = jnp.asarray(rng.integers(0, base_spec.max_a + 1, (nb, h, w, cin)),
                      jnp.int32)
    q_w = jnp.asarray(rng.integers(0, base_spec.max_w + 1,
                                   (fh, fw, cin, co)), jnp.int32)
    want = np.asarray(ref.conv2d_i32_ref(q_x, q_w, padding=padding))

    best, base_us, tried = None, None, 0
    for spec in packing.layout_family(base_spec.w_bits, base_spec.a_bits,
                                      base_spec):
        cp = -(-cin // spec.n_pack)
        if weight_store == "dense":
            cdim = -(-cin // (32 // spec.w_bits))
            k_full = cin
        else:
            cdim, k_full = cp, None
        entry = tune_packed_conv2d(
            (nb, h, w, cp), (fh, fw, cdim, co), spec, padding=padding,
            backend=backend, weight_store=weight_store, k_full=k_full,
            vmem_budget=vmem_budget, cache=cache,
            max_candidates=max_candidates, repeats=repeats, force=force,
            seed=seed)
        xp = packing.pack_activations(q_x, spec, axis=-1)
        if weight_store == "dense":
            wp = ops.dense_store_conv_weights(q_w, spec.w_bits)
        else:
            wp = packing.pack_weights(q_w, spec, axis=2)
        got = np.asarray(ops.packed_conv2d(
            xp, wp, spec, padding=padding, backend=backend,
            weight_store=weight_store, k_full=k_full))
        if not np.array_equal(got, want):
            warnings.warn(f"layout candidate {spec} failed bit-exactness "
                          f"at x={x_shape} w={w_shape}; excluded",
                          stacklevel=2)
            continue
        tried += 1
        us = float(entry["wall_us"])
        if spec == base_spec:
            base_us = us
        if best is None or us < best[0]:
            best = (us, spec)

    us, spec = best
    layout_entry = {"spec": str(spec), "wall_us": round(us, 2),
                    "base_spec": str(base_spec),
                    "base_us": (round(base_us, 2) if base_us is not None
                                else None),
                    "candidates": tried}
    _store(cache, key, layout_entry)
    return layout_entry


def _layout_from_entry(entry: dict | None, w_bits: int,
                       a_bits: int) -> PackSpec | None:
    """Decode + sanity-check a layout entry; None on any mismatch (the
    caller then falls back to the config-derived spec)."""
    if not isinstance(entry, dict) or not isinstance(entry.get("spec"), str):
        return None
    try:
        spec = PackSpec.parse(entry["spec"])
    except ValueError:
        return None
    if spec.w_bits != w_bits or spec.a_bits != a_bits or not spec.feasible:
        return None
    return spec


def matmul_layout_for(k: int, n: int, base_spec: PackSpec, *,
                      backend: str = "auto",
                      weight_store: str = "lanes") -> PackSpec:
    """The per-layer *chosen* lane layout for a [*, k] x [k, n] weight.

    Packers (serve/prepare, models/common), planners (serve layer plans) and
    dispatch (dense_apply) all resolve through here against the active
    cache, defaulting to the config-derived ``base_spec`` on miss — an empty
    cache reproduces the fixed-layout behavior exactly.
    """
    backend = plan_lib.resolve_backend(backend)
    entry = lookup(matmul_layout_key(k, n, base_spec.w_bits,
                                     base_spec.a_bits, backend=backend,
                                     weight_store=weight_store))
    return _layout_from_entry(entry, base_spec.w_bits,
                              base_spec.a_bits) or base_spec


def conv2d_layout_for(x_shape: tuple, w_shape: tuple,
                      base_spec: PackSpec, *, padding: str = "SAME",
                      backend: str = "auto",
                      weight_store: str = "lanes") -> PackSpec:
    """Chosen lane layout for a conv2d (unpacked shapes; see
    matmul_layout_for)."""
    backend = plan_lib.resolve_backend(backend)
    entry = lookup(conv2d_layout_key(tuple(x_shape), tuple(w_shape),
                                     base_spec.w_bits, base_spec.a_bits,
                                     padding=padding, backend=backend,
                                     weight_store=weight_store))
    return _layout_from_entry(entry, base_spec.w_bits,
                              base_spec.a_bits) or base_spec


def tune_attention_chunk(b: int, sq: int, skv: int, h: int, kvh: int,
                         hd: int, *, kv_bits: int = 0,
                         cache: TuningCache | None = None,
                         repeats: int = 3, force: bool = False,
                         seed: int = 0) -> dict:
    """Benchmark the q-chunk of the fused-dequant attention loop for one
    (batch, q-len, kv-len, heads, head-dim, kv_bits) signature."""
    from repro.models import attention as attn

    cache = cache if cache is not None else active_cache()
    key = attention_key(b, sq, skv, h, kvh, hd, kv_bits)
    if not force:
        hit = cache.lookup(key)
        if hit is not None:
            return hit
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, sq, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, skv, kvh, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, skv, kvh, hd)), jnp.float32)
    if kv_bits in (8, 4, 2):
        qk, sk = attn._kv_quantize(k, kv_bits)
        qv, sv = attn._kv_quantize(v, kv_bits)

        def kv_fn():
            return (attn._kv_dequantize(qk, sk, jnp.float32, kv_bits, hd),
                    attn._kv_dequantize(qv, sv, jnp.float32, kv_bits, hd))
    else:
        def kv_fn():
            return k, v
    kv_pos = jnp.arange(skv)
    q_pos = jnp.broadcast_to(jnp.arange(sq)[None, :], (b, sq))

    def mask_fn(qpos):
        return kv_pos[None, None, :] <= qpos[:, :, None]

    best, heuristic_us = None, None
    cands = [c for c in ATTN_CHUNKS if c <= max(sq, ATTN_CHUNKS[0])]
    default = 512
    if default not in cands:
        cands.append(default)
    for chunk in cands:
        fn = jax.jit(lambda q, c=chunk: attn._chunked_attention(
            q, kv_fn, mask_fn, q_pos, c))
        us = measure_us(fn, q, repeats=repeats)
        if chunk == default:
            heuristic_us = us
        if best is None or us < best[0]:
            best = (us, chunk)
    us, chunk = best
    entry = {"q_chunk": int(chunk), "wall_us": round(us, 2),
             "heuristic_us": round(heuristic_us, 2),
             "candidates": len(cands)}
    _store(cache, key, entry)
    return entry


@functools.lru_cache(maxsize=None)
def attention_chunk_for(b: int, sq: int, skv: int, h: int, kvh: int,
                        hd: int, kv_bits: int = 0,
                        default: int = 512) -> int:
    """Tuned q-chunk for a fused-attention signature (``default`` on miss).
    Consulted at trace time by models/attention.attention_apply."""
    entry = lookup(attention_key(b, sq, skv, h, kvh, hd, kv_bits))
    if entry and isinstance(entry.get("q_chunk"), int):
        return entry["q_chunk"]
    return default


def attention_decode_candidates(skv: int, page_size: int | None,
                                kvh: int, hd: int, groups: int,
                                budget: int) -> list[int]:
    """block_k candidates (KV rows per group) under the VMEM budget;
    paged shapes are rounded to whole pages and deduped."""
    cands = []
    for bk in _pow2_cap(ATTN_DECODE_SPLITS, skv):
        if page_size:
            bk = max(1, min(bk // page_size, -(-skv // page_size))) \
                * page_size
        bk = min(bk, skv)
        if bk in cands:
            continue
        if plan_lib.attention_decode_working_set(bk, kvh, hd,
                                                 groups) <= budget:
            cands.append(bk)
    return cands or [min(page_size or skv, skv)]


def tune_attention_decode(b: int, skv: int, h: int, kvh: int, hd: int, *,
                          kv_bits: int = 0, page_size: int | None = None,
                          backend: str = "auto",
                          vmem_budget: int | None = None,
                          cache: TuningCache | None = None,
                          repeats: int = 3, force: bool = False,
                          seed: int = 0) -> dict:
    """Benchmark the kv-split grid of the fused flash-decoding attention
    (kernels/ulppack_attention.py, DESIGN.md §20) for one decode signature
    and persist the winner.

    The synthetic workload matches the serving decode shape: sq == 1
    queries against a ``skv``-row stored cache (paged: a pool of
    ``skv / page_size`` pages behind an identity block table) with every
    row ~2/3 live — the dead-split skip is part of what the grid trades
    off, so candidates must see some dead tail.
    """
    from repro.kernels import ulppack_attention  # registers the backends
    from repro.models import attention as attn

    backend = plan_lib.resolve_backend(backend)
    cache = cache if cache is not None else active_cache()
    key = attention_decode_key(b, skv, h, kvh, hd, kv_bits,
                               page_size=page_size, backend=backend)
    if not force:
        hit = cache.lookup(key)
        if hit is not None:
            return hit
    budget = vmem_budget or int(hw.VMEM_PER_CORE * plan_lib.VMEM_FRACTION)
    groups = max(1, h // kvh)
    heur = plan_lib.plan_attention_decode(
        b, skv, h, kvh, hd, kv_bits, page_size=page_size, backend=backend,
        vmem_budget=vmem_budget, use_tuning_cache=False)

    rng = np.random.default_rng(seed)
    k = jnp.asarray(rng.normal(size=(b, skv, kvh, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, skv, kvh, hd)), jnp.float32)
    if kv_bits in (8, 4, 2):
        qk, sk = attn._kv_quantize(k, kv_bits)
        qv, sv = attn._kv_quantize(v, kv_bits)
        kv = {"k": qk, "v": qv, "k_scale": sk, "v_scale": sv}
    else:
        kv = {"k": k, "v": v}
    bt = None
    if page_size:
        n_pages = skv // page_size
        # the lane-flat page pool (attention.init_paged_kv_cache)
        kv = {name: buf.reshape(b * n_pages, page_size, -1)
              for name, buf in kv.items()}
        bt = jnp.asarray(np.arange(b * n_pages).reshape(b, n_pages),
                         jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, 1, h, hd)), jnp.float32)
    live = max(1, (2 * skv) // 3)
    valid_len = jnp.full((b,), live, jnp.int32)
    qpos = jnp.full((b, 1), live - 1, jnp.int32)

    cands = attention_decode_candidates(skv, page_size, kvh, hd, groups,
                                        budget)
    if heur.block_k not in cands:
        cands.append(heur.block_k)

    best, heuristic_us = None, None
    for bk in cands:
        chunks = max(1, bk // page_size) if page_size else 1
        ws = plan_lib.attention_decode_working_set(bk, kvh, hd, groups)
        plan = dataclasses.replace(heur, block_k=bk, chunks=chunks,
                                   vmem_bytes=ws, source="tuned")
        fn = jax.jit(functools.partial(
            ulppack_attention.fused_decode_attention, kv_bits=kv_bits,
            hd=hd, plan=plan, block_tables=bt))
        us = measure_us(fn, q, kv, valid_len, qpos, repeats=repeats)
        if bk == heur.block_k:
            heuristic_us = us
        if best is None or us < best[0]:
            best = (us, ws, bk, chunks)

    us, ws, bk, chunks = best
    entry = _entry((us, ws), heuristic_us, len(cands),
                   block_k=bk, chunks=chunks)
    _store(cache, key, entry)
    return entry
