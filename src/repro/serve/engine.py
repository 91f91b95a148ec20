"""Continuous-batching serving engine: chunked prefill + ragged decode.

Scheduler shape (DESIGN.md §12 "Serving scheduler"): requests wait in a
bounded queue (backpressure), an admission pass moves them into free batch
slots, prompts stream through the jitted chunked-prefill step — [B, chunk]
token windows per slot, so admission costs O(prompt_len / chunk) launches
at batched arithmetic intensity instead of O(prompt_len) batch-1 decode
steps — and live slots decode lockstep-free: every slot carries its own
position, cache writes land at per-slot offsets (``cache_valid`` /
vector ``cache_index`` in models/lm.forward), and sampling (greedy /
temperature / top-k) is per slot.  While some slots prefill, decode-phase
slots take the same scheduler tick in a decode launch of their own,
finished sequences retire immediately, and freed slots are re-admitted
the same step.

Both steps run the paper's packed integer kernels via
prepare.prepare_serving_params (quant_mode='packed'); KernelPlans for the
decode and prefill row counts are fixed at engine init (paper §IV: one
execution plan per layer, chosen offline).

With ``EngineConfig(paged=True)`` the slot-contiguous KV cache becomes a
refcounted page pool behind per-slot block tables (serve/pages.py,
DESIGN.md §18): admission reserves pages instead of max_len slots, prompt
prefixes are shared via a radix index with copy-on-write on divergence,
and retirement frees pages — the HBM budget then bounds *physical* pages
while ``max_batch`` bounds *logical* slots.

Every tick is traced on the profiler's clock (``jax.profiler``
``TraceAnnotation`` host spans, recorded only while a trace is on):

    engine.step                    one scheduler tick
      engine.admit                 admission (metadata: admitted uids)
      engine.prefill_pass          (metadata: uids consuming prompt)
        engine.batch               numpy batch, block tables, device puts
          engine.cow               one per page copied (src, dst page)
        engine.launch.prefill      the jitted call (dispatch only)
        engine.logits              device-to-host copy of the logits
        engine.draft_prefill       speculative draft's own window
        engine.sample              sampling of every row of the pass
      engine.decode_pass           the same, with engine.launch.decode
      engine.speculative_pass      engine.batch, engine.launch.draft,
                                   engine.drafted, engine.launch.verify,
                                   engine.logits, engine.accept
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as Span

from repro.launch import steps as steps_lib
from repro.models import lm
from repro.serve import pages as pages_lib
from repro.serve import speculative as speculative_lib
from repro.serve.config import EngineConfig, SamplingParams
from repro.serve.prepare import (build_layer_plans, cache_bytes_per_slot,
                                 cache_page_bytes, prepare_serving_params)

__all__ = ["EngineConfig", "Metrics", "Request", "SamplingParams",
           "ServingEngine"]


def _uids(span, reqs):
    """Name the requests a span served, only while a trace records."""
    if Span.is_enabled():
        span.set_metadata(uids=" ".join(str(r.uid) for r in reqs))


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray          # [S] int32
    max_new_tokens: int = 16
    sampling: SamplingParams | None = None   # engine default when None
    output: list = dataclasses.field(default_factory=list)
    done: bool = False
    submit_time: float = 0.0
    admit_time: float = 0.0
    first_token_time: float = 0.0
    finish_time: float = 0.0


@dataclasses.dataclass
class Metrics:
    """Engine-level counters (DESIGN.md §12): throughput split by phase,
    admission latency, slot occupancy, backpressure rejections.

    ``prefill_tokens`` counts prompt tokens consumed by chunked prefill;
    ``generated_tokens`` counts every sampled token; ``decode_tokens``
    only those sampled in decode launches, so decode_tok_s divides
    tokens by the wall time of the same launches.  A request's first
    token, sampled in the prefill launch that completes its prompt,
    counts as generated and lands in the prefill time bucket.

    Per-request latency: ``ttft_s`` records one time-to-first-token sample
    per request (submit -> first sampled token, so queue wait counts —
    the number a client sees); ``tpot_s`` one time-per-output-token sample
    per *retired* request with >= 2 output tokens (first token -> finish,
    per subsequent token).  ``report()`` surfaces mean / p50 / p95 of
    both (DESIGN.md §12).

    Speculative decoding (DESIGN.md §19) adds the draft/verify ledger:
    ``drafted_tokens`` counts draft proposals actually considered
    (per-slot ``limit``, not k x cycles), ``accepted_tokens`` those the
    rejection rule kept, ``verify_tokens`` target window rows scored,
    and ``spec_cycles`` draft+verify launch pairs.  ``report()`` derives
    ``acceptance_rate`` = accepted / drafted — the knob that decides
    whether k was too ambitious for the draft's fidelity.  Committed
    tokens still land in ``decode_tokens``, so ``decode_tok_s`` stays
    directly comparable with a non-speculative engine.
    """
    prefill_tokens: int = 0
    generated_tokens: int = 0
    decode_tokens: int = 0
    prefill_time_s: float = 0.0
    decode_time_s: float = 0.0
    admitted: int = 0
    retired: int = 0
    rejected: int = 0
    steps: int = 0
    slot_steps_live: int = 0
    slot_steps_total: int = 0
    admission_wait_s: float = 0.0
    drafted_tokens: int = 0
    accepted_tokens: int = 0
    verify_tokens: int = 0
    spec_cycles: int = 0
    ttft_s: list = dataclasses.field(default_factory=list)
    tpot_s: list = dataclasses.field(default_factory=list)

    @staticmethod
    def _dist(samples) -> dict:
        if not samples:
            return {"mean": 0.0, "p50": 0.0, "p95": 0.0}
        arr = np.asarray(samples, np.float64)
        return {"mean": round(float(arr.mean()), 5),
                "p50": round(float(np.percentile(arr, 50)), 5),
                "p95": round(float(np.percentile(arr, 95)), 5)}

    def report(self) -> dict:
        def div(a, b):
            return a / b if b else 0.0
        return {
            "prefill_tokens": self.prefill_tokens,
            "generated_tokens": self.generated_tokens,
            "decode_tokens": self.decode_tokens,
            "prefill_tok_s": round(div(self.prefill_tokens,
                                       self.prefill_time_s), 1),
            "decode_tok_s": round(div(self.decode_tokens,
                                      self.decode_time_s), 1),
            "admitted": self.admitted,
            "retired": self.retired,
            "rejected": self.rejected,
            "steps": self.steps,
            "occupancy": round(div(self.slot_steps_live,
                                   self.slot_steps_total), 3),
            "mean_admission_wait_s": round(div(self.admission_wait_s,
                                               self.admitted), 5),
            "drafted_tokens": self.drafted_tokens,
            "accepted_tokens": self.accepted_tokens,
            "verify_tokens": self.verify_tokens,
            "spec_cycles": self.spec_cycles,
            "acceptance_rate": round(div(self.accepted_tokens,
                                         self.drafted_tokens), 3),
            "ttft_s": self._dist(self.ttft_s),
            "tpot_s": self._dist(self.tpot_s),
        }


class ServingEngine:
    """Admission scheduler over chunked prefill + ragged decode (module
    docstring; scheduler design in DESIGN.md §12)."""

    def __init__(self, cfg, params, *, config: EngineConfig | None = None,
                 mesh=None, **legacy):
        # One constructor path (DESIGN.md §17): a frozen, pre-validated
        # EngineConfig.  ``mesh`` stays a direct argument because it is a
        # live placement object (devices), not serializable configuration.
        # The PR 7 deprecation shim for the old 12-keyword surface served
        # its one-release grace period and is gone.
        if legacy:
            raise TypeError(
                f"ServingEngine no longer accepts engine keywords (got "
                f"{sorted(legacy)}); pass config=EngineConfig(...) from "
                f"repro.serve.config instead")
        config = config if config is not None else EngineConfig()
        self.config = config
        packed = config.packed
        self.cfg = cfg
        # Mesh-native serving (DESIGN.md §15): with a mesh, a ShardPlan
        # makes the cross-device layout explicit — packed weights
        # column-parallel (word boundaries shard-local), caches sharded on
        # the kv-head axis — and params/caches are placed before the steps
        # are jitted, so GSPMD partitions both jitted steps against
        # committed shardings.  mesh=None (or model axis 1) degrades to
        # the single-device layout: every spec guards to replicated.
        self.mesh = mesh
        self.shard_plan = None
        self._tp_axis = None
        if mesh is not None:
            from repro.serve.shard import ShardPlan
            self.shard_plan = ShardPlan(mesh)
            if self.shard_plan.model_shards > 1:
                self._tp_axis = self.shard_plan.axis
        # Slot capacity is cache-bytes-aware: with an explicit HBM cache
        # budget the engine admits budget // bytes-per-slot concurrent
        # sequences, so quantized caches (cfg.quant.kv_bits in {8, 4, 2})
        # convert their density directly into batch slots — the capacity
        # rule itself lives in EngineConfig.slots_for (DESIGN.md §13).
        # Paged mode (DESIGN.md §18) changes the capacity unit: the budget
        # buys a pool of pages (EngineConfig.pages_for), logical slots are
        # bounded only by max_batch, and each admission reserves just the
        # pages its request can actually write — shared prompt prefixes
        # and short sequences stop stranding whole max_len slots.
        kv_bits = getattr(cfg.quant, "kv_bits", 0)
        self.paged = config.paged
        self.page_size = config.page_size
        self.cache_bytes_per_slot = cache_bytes_per_slot(cfg, config.max_len)
        self.hbm_cache_budget = config.hbm_cache_budget
        if self.paged:
            if cfg.sliding_window:
                raise ValueError(
                    "paged KV cache and the sliding-window ring layout do "
                    "not compose (attention rejects block_tables there); "
                    "use paged=False for sliding-window configs")
            pages_lib.validate_page_size(self.page_size, kv_bits)
            self.page_bytes = cache_page_bytes(cfg, self.page_size)
            if self.page_bytes == 0:
                raise ValueError(
                    "paged=True requires at least one attention layer "
                    "(nothing pageable in an attention-free stack)")
            self.pages_per_slot = -(-config.max_len // self.page_size)
            self.num_pages = config.pages_for(self.page_bytes,
                                              self.pages_per_slot)
            # admission-time estimate: what one worst-case (no-sharing,
            # full-extent) request would pin
            self.cache_bytes_per_slot = self.pages_per_slot * self.page_bytes
            max_batch = config.max_batch
            # prefix skip is only token-exact when every layer's state is
            # reconstructible from the shared pages — i.e. a pure-attention
            # decoder stack (recurrent layers carry unpaged per-slot state;
            # cross-attention caches key off encoder output, not prompt
            # ids).  Paging without sharing still works for those.
            self._share = (config.prefix_sharing
                           and not cfg.is_encoder_decoder
                           and all(cfg.layer_kind(i) == "attn"
                                   for i in range(cfg.num_layers)))
        else:
            max_batch = config.slots_for(self.cache_bytes_per_slot)
        self.max_batch = max_batch
        self.max_len = config.max_len
        self.prefill_chunk = config.prefill_chunk
        if cfg.sliding_window:
            # ring caches admit only token-by-token prefill: a >1-token
            # window would overwrite ring slots still visible to earlier
            # queries of the same window (attention rejects that case)
            self.prefill_chunk = 1
        self.max_queue = config.max_queue
        self.sampling = config.sampling
        self.params = prepare_serving_params(
            params, cfg, dense_store=config.dense_store) \
            if packed else params
        # Kernel plans are fixed at engine init (paper §IV: one execution
        # plan per layer, chosen offline) for both jitted row counts —
        # decode (max_batch rows) and chunked prefill (max_batch * chunk);
        # under a shard plan they are built against per-shard local output
        # widths, what one device actually executes.
        # ``autotune=True`` warm-tunes missing signatures first (the
        # tune-once-offline deployment pass, DESIGN.md §14).
        self.plans = build_layer_plans(
            self.params, cfg, batch_rows=max_batch,
            prefill_rows=max_batch * self.prefill_chunk,
            autotune=config.autotune,
            shard_plan=self.shard_plan) if packed else {}
        if self.shard_plan is not None:
            self.params = self.shard_plan.place_params(self.params)
        # Jitted steps are memoized per (cfg, tp axis, mesh devices): a
        # replica fleet (serve/router.Router) over one model shares a
        # single trace/compile across layout-identical replicas instead of
        # paying it N times.
        self._decode, self._prefill = steps_lib.jitted_serving_steps(
            cfg, kv_shard_axis=self._tp_axis, mesh=self.mesh)
        self._queue: deque[Request] = deque()
        if self.paged:
            self.caches = lm.init_caches(cfg, max_batch, self.max_len,
                                         dtype=jnp.bfloat16,
                                         page_size=self.page_size,
                                         num_pages=self.num_pages)
            self.pool = pages_lib.PagePool(self.num_pages, self.page_size,
                                           kv_bits)
            self.block_tables = np.zeros((max_batch, self.pages_per_slot),
                                         np.int32)
            self._slot_extent = [0] * max_batch   # table entries in use
            self._slot_spare: list = [[] for _ in range(max_batch)]
            self.peak_live_slots = 0
        else:
            self.caches = lm.init_caches(cfg, max_batch, self.max_len,
                                         dtype=jnp.bfloat16)
        if self.shard_plan is not None:
            self.caches = self.shard_plan.place_caches(
                self.caches, cfg, max_batch, paged=self.paged)
        # batch-1 fresh-cache template: admission resets a slot's rows from
        # it (recurrent states have non-zero init, e.g. mLSTM m = -inf)
        self._fresh = lm.init_caches(cfg, 1, self.max_len,
                                     dtype=jnp.bfloat16)
        # Speculative decoding (DESIGN.md §19): a DraftModel re-packs the
        # SAME checkpoint at draft_w_bits with its own caches (and, paged,
        # its own small page pool), and pure-decode passes become
        # draft-k + verify-in-one-call cycles (_speculative_pass).
        self.spec = None
        self._verify = None
        if config.speculative_k:
            self._validate_speculative(cfg)
            self.spec = speculative_lib.DraftModel(
                cfg, params, config, max_batch=max_batch,
                max_len=self.max_len, shard_plan=self.shard_plan,
                mesh=self.mesh, tp_axis=self._tp_axis)
            _, self._verify = steps_lib.jitted_speculative_steps(
                cfg, self.spec.cfg, config.speculative_k,
                kv_shard_axis=self._tp_axis, mesh=self.mesh)
        # per-slot bookkeeping
        self.slot_req: list = [None] * max_batch
        self.slot_pos = np.zeros(max_batch, np.int32)   # tokens in cache
        self.slot_fed = np.zeros(max_batch, np.int32)   # prompt consumed
        self._slot_rng: list = [None] * max_batch
        self._finished: list = []
        self.metrics = Metrics()

    @staticmethod
    def _validate_speculative(cfg):
        """Speculation needs a pure-attention decoder whose chunked
        writes equal sequential writes — the verify-window rollback
        argument (DESIGN.md §19) does not hold for ring caches,
        recurrent state, or position schemes the draft step does not
        model."""
        problems = []
        if cfg.is_encoder_decoder:
            problems.append("encoder-decoder stacks")
        if cfg.sliding_window:
            problems.append("sliding-window (ring) KV caches")
        if cfg.mrope:
            problems.append("M-RoPE position ids")
        if any(cfg.layer_kind(i) != "attn" for i in range(cfg.num_layers)):
            problems.append("non-attention (recurrent) layers")
        if problems:
            raise ValueError(
                f"speculative_k > 0 requires a pure-attention decoder "
                f"stack; this config has: {', '.join(problems)}")

    def _mesh_ctx(self):
        """Announce the serving mesh to sharding.constrain() for the
        duration of a jitted-step call — constrain() and the sharded-vocab
        embedding path read the active mesh at trace time, so the first
        call under this context bakes the mesh into both executables."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.parallel.sharding import activation_mesh
        return activation_mesh(self.mesh)

    # ------------------------------------------------------------------
    # Submission / admission
    # ------------------------------------------------------------------

    def submit(self, req: Request) -> bool:
        """Queue a request.  Returns False (rejected, counted in metrics)
        when the backpressure cap ``max_queue`` is hit."""
        if len(req.prompt) + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {req.uid}: prompt ({len(req.prompt)}) + "
                f"max_new_tokens ({req.max_new_tokens}) exceeds engine "
                f"max_len ({self.max_len})")
        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            self.metrics.rejected += 1
            return False
        if not req.submit_time:
            # the fleet Router stamps submit_time at fleet admission so a
            # spilled request's TTFT includes its spillover wait
            req.submit_time = time.perf_counter()
        self._queue.append(req)
        return True

    def _reset_slot(self, slot: int):
        """Restore one batch row of the recurrent-state cache leaves to
        their freshly-initialized values (mamba conv/ssm, xLSTM C/n/m —
        non-zero inits included).  Attention rows need no reset: their
        validity is re-derived per call from cache_index/cache_valid, so
        stale entries are masked until overwritten."""

        def reset(cur, fresh):
            return cur.at[slot:slot + 1].set(fresh.astype(cur.dtype))

        out = []
        for cur_layer, fresh_layer in zip(self.caches, self._fresh):
            layer = dict(cur_layer)
            for kind, sub in cur_layer.items():
                if kind == "attn" or sub is None:
                    continue
                layer[kind] = jax.tree.map(reset, sub, fresh_layer[kind])
            out.append(layer)
        self.caches = out

    # -- paged reservation / copy-on-write -----------------------------

    def _reserve_pages(self, slot: int, req: Request) -> int | None:
        """Reserve every page ``req`` can write, all-or-nothing.

        Positions written span ``[0, W)`` with ``W = len(prompt) +
        max_new_tokens - 1`` (the last sampled token is returned, never
        cached).  A cached prefix match (capped at ``len(prompt) - 1``,
        match_prefix docstring) contributes shared pages — retained, not
        copied; fresh pages cover the rest, plus COW spares for the two
        divergence writes a request can hit: its first write into a
        partially-shared page, and its first generated token landing in
        the prompt's registered tail page.  Returns the shared token
        count, or None (nothing taken) when the pool cannot cover it —
        the request stays queued, FIFO preserved.
        """
        ps = self.page_size
        n_prompt = len(req.prompt)
        written = n_prompt + req.max_new_tokens - 1
        n_shared, shared = 0, []
        if self._share:
            n_shared, shared = self.pool.match_prefix(
                req.prompt, max_tokens=n_prompt - 1)
        first_partial = 1 if n_shared % ps else 0
        fill_from = n_shared // ps + first_partial
        fresh = -(-written // ps) - fill_from
        tail_cow = 1 if (self._share and n_prompt % ps
                         and written > n_prompt) else 0
        for pg, _rows in shared:             # pin before alloc can evict
            self.pool.retain(pg)
        got = self.pool.alloc(fresh + first_partial + tail_cow)
        if got is None:
            for pg, _rows in shared:
                self.pool.release(pg)
            return None
        table = self.block_tables[slot]
        table[:] = 0
        for i, (pg, _rows) in enumerate(shared):
            table[i] = pg
        table[fill_from:fill_from + fresh] = got[:fresh]
        self._slot_extent[slot] = fill_from + fresh
        self._slot_spare[slot] = got[fresh:]
        if n_shared:
            self.pool.prefix_hits += 1
            self.pool.prefix_hit_tokens += n_shared
        return n_shared

    def _release_slot_pages(self, slot: int):
        for p in self.block_tables[slot][:self._slot_extent[slot]]:
            self.pool.release(int(p))
        for p in self._slot_spare[slot]:
            self.pool.release(int(p))
        self.block_tables[slot][:] = 0
        self._slot_extent[slot] = 0
        self._slot_spare[slot] = []

    def _ensure_writable(self, slot: int, lo: int, hi: int):
        """Copy-on-write ahead of a pass writing positions ``[lo, hi)``:
        any mapped page that is shared (ref > 1) or frozen by the prefix
        index gets a private copy first (reserved spare, else a fresh
        alloc under pressure), so writers never touch shared bytes."""
        ps = self.page_size
        table = self.block_tables[slot]
        for pi in range(lo // ps, -(-hi // ps)):
            pg = int(table[pi])
            if not (self.pool.is_shared(pg) or self.pool.is_immutable(pg)):
                continue
            spare = self._slot_spare[slot]
            if spare:
                dst = spare.pop()
            else:
                got = self.pool.alloc(1)
                if got is None:
                    raise RuntimeError(
                        f"page pool exhausted during copy-on-write for "
                        f"slot {slot} (page {pg}); reservation math must "
                        f"cover every divergence write")
                dst = got[0]
            with Span("engine.cow") as span:
                if Span.is_enabled():
                    span.set_metadata(src=pg, dst=int(dst))
                self.caches = pages_lib.copy_page(self.caches, pg, dst)
            table[pi] = dst
            self.pool.release(pg)
            self.pool.cow_copies += 1

    def _admit(self) -> list:
        """Move queued requests into free slots; returns those admitted."""
        now = time.perf_counter()
        admitted = []
        for slot in range(self.max_batch):
            if self.slot_req[slot] is None and self._queue:
                req = self._queue[0]
                n_shared = 0
                if self.paged:
                    reserved = self._reserve_pages(slot, req)
                    if reserved is None:
                        # head-of-line blocks until pages free: FIFO, no
                        # starvation of large requests by small ones
                        break
                    n_shared = reserved
                self._queue.popleft()
                self._reset_slot(slot)
                self.slot_req[slot] = req
                self.slot_pos[slot] = n_shared
                self.slot_fed[slot] = n_shared
                if self.spec is not None:
                    # the draft replays the FULL prompt (no prefix skip:
                    # its cache has no rows for skipped positions)
                    self.spec.begin_slot(slot, req)
                sp = req.sampling or self.sampling
                self._slot_rng[slot] = np.random.default_rng(
                    (sp.seed, req.uid & 0xFFFFFFFF))
                req.admit_time = now
                self.metrics.admitted += 1
                self.metrics.admission_wait_s += now - req.submit_time
                admitted.append(req)
        return admitted

    # ------------------------------------------------------------------
    # Stepping
    # ------------------------------------------------------------------

    def step(self) -> bool:
        """One scheduler tick: admit, then the batched model passes —
        chunked prefill while any slot is mid-prompt (followed by a decode
        launch for the decode-phase slots), else a single-token ragged
        decode."""
        with Span("engine.step"):
            with Span("engine.admit") as span:
                _uids(span, self._admit())
            live = [s for s in range(self.max_batch)
                    if self.slot_req[s] is not None]
            if not live:
                return False
            self.metrics.steps += 1
            self.metrics.slot_steps_live += len(live)
            self.metrics.slot_steps_total += self.max_batch
            if self.paged:
                self.peak_live_slots = max(self.peak_live_slots, len(live))
            prefilling = any(
                self.slot_fed[s] < len(self.slot_req[s].prompt) for s in live)
            if self.spec is not None:
                # the draft may still be replaying a prefix-skipped prompt
                # after the target finished; keep the pass a prefill pass
                # (speculation only runs on pure-decode passes)
                prefilling = prefilling or any(
                    not self.spec.prompt_done(s, self.slot_req[s])
                    for s in live)
            t0 = time.perf_counter()
            if prefilling:
                n_prompt, decoding = self._prefill_pass(live)
                t1 = time.perf_counter()
                self.metrics.prefill_time_s += t1 - t0
                self.metrics.prefill_tokens += n_prompt
                if decoding:
                    self._decode_pass(decoding)
                    self.metrics.decode_time_s += time.perf_counter() - t1
            elif self.spec is not None:
                self._speculative_pass(live)
                self.metrics.decode_time_s += time.perf_counter() - t0
            else:
                self._decode_pass(live)
                self.metrics.decode_time_s += time.perf_counter() - t0
            return True

    def _positions3(self, index: np.ndarray, width: int):
        pos = index[:, None] + np.arange(width, dtype=np.int32)[None, :]
        return jnp.asarray(
            np.broadcast_to(pos[None], (3, self.max_batch, width)).copy())

    def _prefill_pass(self, live) -> tuple[int, list]:
        """The prefill-chunk launch for slots still mid-prompt.  Returns
        (prompt tokens consumed, decode-phase slots).  Those slots are
        dead rows here and take their step in the decode program right
        after: a row's numbers then never depend on which program it rode
        in, so a request gets the same tokens whatever it is batched with
        (DESIGN.md §12)."""
        with Span("engine.prefill_pass") as span:
            with Span("engine.batch"):
                c = self.prefill_chunk
                tokens = np.zeros((self.max_batch, c), np.int32)
                index = np.zeros(self.max_batch, np.int32)
                valid = np.zeros(self.max_batch, np.int32)
                take = {}
                decoding = []
                n_prompt = 0
                for s in live:
                    req = self.slot_req[s]
                    index[s] = self.slot_pos[s]
                    rem = len(req.prompt) - int(self.slot_fed[s])
                    if rem > 0:        # mid-prompt: its next chunk window
                        t = min(c, rem)
                        fed = int(self.slot_fed[s])
                        tokens[s, :t] = req.prompt[fed:fed + t]
                        valid[s] = take[s] = t
                        n_prompt += t
                    elif req.output:   # decode phase: the decode program
                        decoding.append(s)
                    # else: target prompt done but the first token is
                    # stashed until the speculative draft finishes its
                    # full-prompt replay — a dead slot (valid 0) here
                batch = {"tokens": jnp.asarray(tokens)}
                if self.cfg.mrope:
                    batch["positions3"] = self._positions3(index, c)
                step_args = ()
                if self.paged:
                    for s in live:
                        lo = int(index[s])
                        self._ensure_writable(s, lo, lo + int(valid[s]))
                    step_args = (jnp.asarray(self.block_tables),)
                dev_index, dev_valid = jnp.asarray(index), jnp.asarray(valid)
            _uids(span, (self.slot_req[s] for s in take))
            logits = None
            if int(valid.sum()):   # all-stash-waiting passes skip it
                with Span("engine.launch.prefill"), self._mesh_ctx():
                    logits, self.caches = self._prefill(
                        self.params, self.caches, batch, dev_index,
                        dev_valid, *step_args)
                with Span("engine.logits"):
                    logits = np.asarray(logits)
            if self.spec is not None:
                self._draft_prefill(live)
            with Span("engine.sample"):
                self._sample_prefill(live, take, logits)
        return n_prompt, decoding

    def _sample_prefill(self, live, take, logits):
        """Advance each prefilled slot; a slot whose prompt just completed
        samples its first token (or parks its logits for the draft)."""
        for s in live:
            req = self.slot_req[s]
            if s in take:
                self.slot_fed[s] += take[s]
                self.slot_pos[s] += take[s]
                if self.slot_fed[s] == len(req.prompt):
                    if self.paged and self._share:
                        self._register_prompt(s, req)
                    if self.spec is None or self.spec.prompt_done(s, req):
                        self._emit_token(s, logits[s],
                                         decode_pass=False)  # first token
                    else:
                        # prefix sharing let the target finish before the
                        # draft's full replay: park the first-token logits
                        self.spec.stash(s, logits[s])
            elif self.spec is not None and self.spec.has_stash(s) \
                    and self.spec.prompt_done(s, req):
                # the draft just caught up: emit the parked first token
                self._emit_token(s, self.spec.pop_stash(s),
                                 decode_pass=False)

    def _draft_prefill(self, live):
        """Feed the speculative draft cache its own prefill window:
        prompt chunks for slots still replaying (from draft position
        ``fed`` — the draft never prefix-skips, DESIGN.md §19), the
        single pending token for decode riders so draft and target
        caches stay position-aligned through mixed passes."""
        spec = self.spec
        with Span("engine.draft_prefill"):
            with Span("engine.batch"):
                c = self.prefill_chunk
                tokens = np.zeros((self.max_batch, c), np.int32)
                index = np.zeros(self.max_batch, np.int32)
                valid = np.zeros(self.max_batch, np.int32)
                fed_take = {}
                for s in live:
                    req = self.slot_req[s]
                    fed = int(spec.fed[s])
                    rem = len(req.prompt) - fed
                    if rem > 0:
                        t = min(c, rem)
                        tokens[s, :t] = req.prompt[fed:fed + t]
                        index[s] = fed
                        valid[s] = fed_take[s] = t
                    elif req.output:
                        tokens[s, 0] = req.output[-1]
                        index[s] = self.slot_pos[s]
                        valid[s] = 1
                if not int(valid.sum()):
                    return
                step_args = (jnp.asarray(spec.block_tables),) \
                    if spec.paged else ()
                args = ({"tokens": jnp.asarray(tokens)}, jnp.asarray(index),
                        jnp.asarray(valid), *step_args)
            with Span("engine.launch.draft_prefill"), self._mesh_ctx():
                _, spec.caches = spec._prefill(spec.params, spec.caches,
                                               *args)
            for s, t in fed_take.items():
                spec.fed[s] += t

    def _register_prompt(self, s: int, req: Request):
        """Hash-cons the just-completed prompt's pages into the prefix
        index (before the first generated token, which may retire the
        slot immediately at max_new_tokens=1): later requests with the
        same prefix share these physical pages instead of re-prefilling."""
        n_pages = -(-len(req.prompt) // self.page_size)
        self.pool.register_prefix(
            req.prompt, [int(p) for p in self.block_tables[s][:n_pages]])

    def _decode_pass(self, live):
        with Span("engine.decode_pass") as span:
            _uids(span, (self.slot_req[s] for s in live))
            with Span("engine.batch"):
                tokens = np.zeros((self.max_batch, 1), np.int32)
                index = np.zeros(self.max_batch, np.int32)
                valid = np.zeros(self.max_batch, np.int32)
                for s in live:
                    req = self.slot_req[s]
                    tokens[s, 0] = req.output[-1] if req.output \
                        else int(req.prompt[-1])
                    index[s] = self.slot_pos[s]
                    valid[s] = 1
                batch = {"tokens": jnp.asarray(tokens)}
                if self.cfg.mrope:
                    batch["positions3"] = self._positions3(index, 1)
                step_args = ()
                if self.paged:
                    for s in live:
                        self._ensure_writable(s, int(index[s]),
                                              int(index[s]) + 1)
                    step_args = (jnp.asarray(self.block_tables),)
                dev_index, dev_valid = jnp.asarray(index), jnp.asarray(valid)
            with Span("engine.launch.decode"), self._mesh_ctx():
                logits, self.caches = self._decode(
                    self.params, self.caches, batch, dev_index, dev_valid,
                    *step_args)
            with Span("engine.logits"):
                logits = np.asarray(logits)
            with Span("engine.sample"):
                for s in live:
                    self.slot_pos[s] += 1
                    self._emit_token(s, logits[s], decode_pass=True)

    def _speculative_pass(self, live):
        """One speculative cycle (DESIGN.md §19): draft up to ``k``
        greedy tokens per slot in a single launch, score the whole
        drafted chain in one ``[B, k+1]`` target verify call (the
        prefill-chunk window shape), then commit the longest
        target-faithful prefix per slot via rejection sampling
        (speculative.accept_tokens) — 1..k+1 tokens for two launches."""
        with Span("engine.speculative_pass") as span:
            _uids(span, (self.slot_req[s] for s in live))
            k = self.config.speculative_k
            spec = self.spec
            with Span("engine.batch"):
                tokens = np.zeros((self.max_batch, 1), np.int32)
                index = np.zeros(self.max_batch, np.int32)
                # dead slots draft at limit -1: limit+1 = 0 gates off every
                # cache write (a paged dead slot's block table row would alias
                # page 0)
                limit = np.full(self.max_batch, -1, np.int32)
                for s in live:
                    req = self.slot_req[s]
                    tokens[s, 0] = req.output[-1] if req.output \
                        else int(req.prompt[-1])
                    index[s] = self.slot_pos[s]
                    # a cycle commits at most limit+1 tokens, so limit =
                    # min(k, remaining-1) never drafts past the request budget
                    # and every cache write stays inside the reserved extent
                    limit[s] = min(k, req.max_new_tokens - len(req.output) - 1)
                d_args = ({"tokens": jnp.asarray(tokens)}, jnp.asarray(index),
                          jnp.asarray(limit))
                if spec.paged:
                    d_args += (jnp.asarray(spec.block_tables),)
            with Span("engine.launch.draft"), self._mesh_ctx():
                drafted, spec.caches = spec._draft(spec.params, spec.caches,
                                                   *d_args)
            with Span("engine.drafted"):
                drafted = np.asarray(drafted)                  # [B, k]
            with Span("engine.batch"):
                win = np.zeros((self.max_batch, k + 1), np.int32)  # [t0, d..]
                win[:, 0] = tokens[:, 0]
                win[:, 1:] = drafted
                valid = np.maximum(limit + 1, 0)
                v_args = ({"tokens": jnp.asarray(win)}, jnp.asarray(index),
                          jnp.asarray(valid))
                if self.paged:
                    for s in live:
                        lo = int(index[s])
                        self._ensure_writable(s, lo, lo + int(valid[s]))
                    v_args += (jnp.asarray(self.block_tables),)
            with Span("engine.launch.verify"), self._mesh_ctx():
                logits, self.caches = self._verify(self.params, self.caches,
                                                   *v_args)
            with Span("engine.logits"):
                logits = np.asarray(logits)                    # [B, k+1, V]
            self.metrics.spec_cycles += 1
            with Span("engine.accept"):
                for s in live:
                    req = self.slot_req[s]
                    lim = int(limit[s])
                    committed = speculative_lib.accept_tokens(
                        logits[s, :lim + 1], drafted[s, :lim],
                        req.sampling or self.sampling, self._slot_rng[s])
                    self.metrics.drafted_tokens += lim
                    self.metrics.accepted_tokens += len(committed) - 1
                    self.metrics.verify_tokens += lim + 1
                    for tok in committed:
                        self.slot_pos[s] += 1
                        self._commit_token(s, int(tok), decode_pass=True)
                        if self.slot_req[s] is None:   # retired mid-window
                            break

    def _emit_token(self, s: int, logits_row: np.ndarray, *,
                    decode_pass: bool):
        """Sample one token from a logits row and commit it — the plain
        (non-speculative) emission path.  Sampling goes through
        speculative.sample_token, the same primitive the speculative
        bonus/resample path uses, so both paths draw from identical
        per-slot distributions and rng streams."""
        req = self.slot_req[s]
        tok = speculative_lib.sample_token(
            logits_row, req.sampling or self.sampling, self._slot_rng[s])
        self._commit_token(s, tok, decode_pass=decode_pass)

    def _commit_token(self, s: int, tok: int, *, decode_pass: bool):
        """Append one already-chosen token to slot ``s``'s request:
        metrics, TTFT/TPOT stamps, and retirement (slot + page release,
        draft pages included) when the request hits max_new_tokens."""
        req = self.slot_req[s]
        req.output.append(int(tok))
        self.metrics.generated_tokens += 1
        if decode_pass:
            self.metrics.decode_tokens += 1
        if len(req.output) == 1:
            req.first_token_time = time.perf_counter()
            self.metrics.ttft_s.append(req.first_token_time
                                       - req.submit_time)
        if len(req.output) >= req.max_new_tokens:
            req.done = True
            req.finish_time = time.perf_counter()
            if len(req.output) > 1:
                self.metrics.tpot_s.append(
                    (req.finish_time - req.first_token_time)
                    / (len(req.output) - 1))
            self._finished.append(req)
            self.metrics.retired += 1
            self.slot_req[s] = None
            if self.paged:
                # page-level retirement: drop this slot's references only;
                # prefix-index pages keep their index ref and stay cached
                self._release_slot_pages(s)
            if self.spec is not None:
                self.spec.release_slot(s)

    # ------------------------------------------------------------------
    # Reporting / draining
    # ------------------------------------------------------------------

    @property
    def num_pending(self) -> int:
        return len(self._queue)

    @property
    def num_live(self) -> int:
        """Occupied batch slots (the Router's load term, with the queue)."""
        return sum(r is not None for r in self.slot_req)

    def take_finished(self) -> list:
        """Hand over every request retired since the last call (the Router
        collects after each fleet tick; run_to_completion uses it too)."""
        done, self._finished = self._finished, []
        return done

    def take_queued(self) -> list:
        """Drain the admission queue WITHOUT serving it: replica drain
        support — the Router re-routes these to other replicas while this
        engine's live slots retire."""
        queued, self._queue = list(self._queue), deque()
        return queued

    def plan_report(self):
        """Flat per-layer plan rows (path + KernelPlan.describe())."""
        return [{"layer": path, **plan.describe()}
                for path, plan in sorted(self.plans.items())]

    def capacity_report(self) -> dict:
        """Cache-capacity accounting: bytes per slot and admitted slots;
        paged engines add physical-vs-logical page counters (pool free /
        live / shared pages, prefix-hit and COW counts, DESIGN.md §18);
        speculative engines add a ``speculative`` section (draft
        precision + draft pool sizing, DESIGN.md §19)."""
        rep = {
            "kv_bits": getattr(self.cfg.quant, "kv_bits", 0) or 16,
            "cache_bytes_per_slot": self.cache_bytes_per_slot,
            "hbm_cache_budget": self.hbm_cache_budget,
            "slots": self.max_batch,
            "paged": self.paged,
        }
        if self.paged:
            rep.update(
                page_size=self.page_size,
                page_bytes=self.page_bytes,
                num_pages=self.num_pages,
                pages_per_slot=self.pages_per_slot,
                # logical slots max_batch vs what worst-case reservations
                # alone would fit — sharing lifts live slots above this
                guaranteed_slots=self.num_pages // self.pages_per_slot,
                peak_live_slot_count=self.peak_live_slots,
                prefix_sharing=self._share,
                **self.pool.report())
        if self.spec is not None:
            rep["speculative"] = self.spec.describe()
        if self.shard_plan is not None:
            rep["shard_plan"] = self.shard_plan.describe()
        return rep

    # ------------------------------------------------------------------
    # Paged-state serialization (Router drain/restore, DESIGN.md §18)
    # ------------------------------------------------------------------

    def export_paged_state(self):
        """(caches, pool_meta): the device-side page pools (every layer's
        paged KV leaves — the bytes behind the warm prefix cache) plus the
        pool's JSON-able bookkeeping.  Drain retires live slots first, so
        what survives is exactly the prefix index and its pages."""
        if not self.paged:
            raise ValueError("export_paged_state on an unpaged engine")
        return self.caches, self.pool.export_meta()

    def import_paged_state(self, caches, pool_meta: dict):
        """Adopt a drained engine's page pools + prefix index (restore
        path, inverse of :meth:`export_paged_state`).  Geometry must match
        this engine's construction — the Router rebuilds the engine from
        the same EngineConfig first."""
        if not self.paged:
            raise ValueError("import_paged_state on an unpaged engine")
        if (pool_meta["num_pages"] != self.num_pages
                or pool_meta["page_size"] != self.page_size):
            raise ValueError(
                f"paged-state geometry mismatch: checkpoint has "
                f"{pool_meta['num_pages']} pages x {pool_meta['page_size']} "
                f"rows, engine was built with {self.num_pages} x "
                f"{self.page_size}")
        self.caches = jax.tree.map(
            lambda tpl, leaf: jnp.asarray(leaf, tpl.dtype),
            self.caches, caches)
        if self.shard_plan is not None:
            self.caches = self.shard_plan.place_caches(
                self.caches, self.cfg, self.max_batch, paged=True)
        self.pool = pages_lib.PagePool.from_meta(pool_meta)

    def run_to_completion(self):
        """Drain queue + slots; returns every request retired since the
        last call.  Retirement is recorded at sample time (not via
        before/after slot snapshots), so a request admitted and finished
        within a single step() is still collected."""
        while self.step():
            pass
        return self.take_finished()
