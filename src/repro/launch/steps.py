"""Step factories: train_step / prefill_step / decode_step.

These are the functions the dry-run lowers and the trainer/server jit.
Quantization modes per step kind (DESIGN.md §2, §5, §12):
  train         -> 'qat'    (LSQ fake-quant, STE grads)
  prefill       -> 'qat'    (compute-bound; on TPU the fused Pallas kernel
                             serves this role — the CPU-lowered dry-run
                             uses fake-quant)
  prefill_chunk -> 'packed' (serving-time chunked prefill over the engine's
                             packed params: [B, chunk] windows per slot at
                             batched arithmetic intensity)
  decode        -> 'packed' (the deployed Sparq integer path; scan-free
                             batched packed dots so roofline FLOPs are
                             exact)

The decode and prefill_chunk steps are cache-template-agnostic: the engine
passes whatever layout ``cfg.quant.kv_bits`` selected (bf16 / int8 /
bit-dense packed words + scales, lm.init_caches), and attention fuses the
unpack+dequant of quantized templates into its q-chunked loop — the jitted
step never materializes a full-precision cache (DESIGN.md §13).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ulppack_attention
from repro.models import lm
from repro.optim import adamw, schedules


#: XLA options of every jitted serving step.  By default XLA may keep a
#: bf16 intermediate in f32 inside a fusion ("excess precision"), so where
#: a value is rounded depends on how the program was fused — and a
#: tensor-parallel step, whose collectives split fusions, would then round
#: differently from the one-device step and drift to other greedy tokens.
#: Rounding every bf16 value where the program says keeps the served
#: tokens independent of the mesh.
SERVING_XLA_OPTIONS = {"xla_allow_excess_precision": False}


def quant_mode_for(cfg, kind: str) -> str:
    if not cfg.quant.enabled:
        return "none"
    return {"train": "qat", "prefill": "qat", "prefill_chunk": "packed",
            "decode": "packed"}[kind]


# ---------------------------------------------------------------------------
# Train
# ---------------------------------------------------------------------------

def make_train_step(cfg, *, adamw_cfg: adamw.AdamWConfig | None = None,
                    schedule: str = "cosine", peak_lr: float = 3e-4,
                    warmup_steps: int = 100, total_steps: int = 10_000,
                    clip_norm: float = 1.0, compress_grads: bool = False):
    adamw_cfg = adamw_cfg or adamw.AdamWConfig(
        eightbit_moments=cfg.parallel.eightbit_moments)
    sched = schedules.get_schedule(schedule)
    qmode = quant_mode_for(cfg, "train")
    remat = cfg.parallel.remat != "none"
    n_micro = max(1, cfg.parallel.microbatches)

    def loss_of(params, mb):
        logits, aux, _ = lm.forward(params, cfg, mb, quant_mode=qmode,
                                    remat=remat)
        loss, ce = lm.loss_fn(logits, mb["labels"], aux)
        return loss, ce

    grad_fn = jax.value_and_grad(loss_of, has_aux=True)

    def split_micro(batch):
        def sp(x):
            if x.ndim >= 2 and x.shape[0] == 3:      # positions3 [3,B,S]
                return jnp.moveaxis(
                    x.reshape(3, n_micro, x.shape[1] // n_micro,
                              *x.shape[2:]), 1, 0)
            return x.reshape(n_micro, x.shape[0] // n_micro, *x.shape[1:])
        return jax.tree.map(sp, batch)

    def train_step(state, batch):
        params, opt_state, step = (state["params"], state["opt_state"],
                                   state["step"])
        lr = sched(step, peak_lr=peak_lr, warmup_steps=warmup_steps,
                   total_steps=total_steps)

        if n_micro == 1:
            (loss, ce), grads = grad_fn(params, batch)
        else:
            micro = split_micro(batch)

            from repro.parallel.sharding import constrain_like_params

            def body(acc, mb):
                (l, c), g = grad_fn(params, mb)
                g_acc, l_acc, c_acc = acc
                g_new = constrain_like_params(
                    jax.tree.map(jnp.add, g_acc, g), cfg)
                return (g_new, l_acc + l, c_acc + c), None

            zeros = constrain_like_params(jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params), cfg)
            (grads, loss, ce), _ = jax.lax.scan(
                body, (zeros, 0.0, 0.0), micro)
            grads = jax.tree.map(lambda g: g / n_micro, grads)
            loss, ce = loss / n_micro, ce / n_micro

        if compress_grads:
            from repro.parallel import collectives
            grads, state = collectives.compress_grads_with_feedback(
                grads, state)

        grads, gnorm = adamw.clip_by_global_norm(grads, clip_norm)
        updates, opt_state = adamw.update(grads, opt_state, params, lr,
                                          adamw_cfg)
        params = adamw.apply_updates(params, updates)
        new_state = dict(state)
        new_state.update(params=params, opt_state=opt_state, step=step + 1)
        metrics = {"loss": loss, "ce": ce, "grad_norm": gnorm, "lr": lr}
        return new_state, metrics

    return train_step


def make_train_state(params, adamw_cfg: adamw.AdamWConfig | None = None,
                     error_feedback: bool = False, cfg=None):
    if adamw_cfg is None:
        adamw_cfg = adamw.AdamWConfig(
            eightbit_moments=cfg.parallel.eightbit_moments if cfg is not None
            else False)
    state = {"params": params,
             "opt_state": adamw.init(params, adamw_cfg),
             "step": jnp.zeros((), jnp.int32)}
    if error_feedback:
        state["error_feedback"] = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return state


# ---------------------------------------------------------------------------
# Serve
# ---------------------------------------------------------------------------

def make_prefill_step(cfg, max_len: int):
    qmode = quant_mode_for(cfg, "prefill")

    def prefill_step(params, batch):
        from repro.models import common as _c
        b = batch["tokens"].shape[0]
        caches = lm.init_caches(cfg, b, max_len,
                                dtype=_c.dtype_of(cfg.compute_dtype))
        logits, _, caches = lm.forward(params, cfg, batch,
                                       quant_mode=qmode, caches=caches)
        return logits[:, -1], caches

    return prefill_step


def make_decode_step(cfg, *, kv_shard_axis: str | None = None):
    """Single-token decode step.

    ``index`` scalar = lockstep (all rows share one position, the legacy
    path); ``index`` [B] = per-slot positions for ragged continuous
    batching, with optional ``valid`` [B] (1 = live slot, 0 = dead slot:
    no cache write, output ignored).  See DESIGN.md §12.

    ``kv_shard_axis`` names the mesh axis the serving ShardPlan sharded
    the KV-cache kv-head axis over (None = single-device serving); the
    attention write path constrains its quantize/pack/scatter to stay
    head-local on that axis (DESIGN.md §15).

    A paged engine additionally passes ``block_tables`` [B, n_pages]
    (host-side numpy, replicated under a mesh) and paged pool caches;
    omitting it keeps the slot-contiguous path byte-for-byte unchanged
    (DESIGN.md §18).
    """
    qmode = quant_mode_for(cfg, "decode")

    def decode_step(params, caches, batch, index, valid=None,
                    block_tables=None):
        b = batch["tokens"].shape[0]
        dec = dict(batch)
        idx = jnp.asarray(index, jnp.int32)
        if idx.ndim == 0:
            dec["positions"] = jnp.full((b, 1), idx, jnp.int32)
        else:
            dec["positions"] = idx[:, None]
        logits, _, caches = lm.forward(params, cfg, dec, quant_mode=qmode,
                                       caches=caches, cache_index=idx,
                                       cache_valid=valid,
                                       kv_shard_axis=kv_shard_axis,
                                       block_tables=block_tables)
        return logits[:, -1], caches

    return decode_step


def make_prefill_chunk_step(cfg, *, kv_shard_axis: str | None = None):
    """Chunked-prefill step: consumes a [B, chunk] token window per slot.

    ``index`` [B] is each slot's write offset (tokens already in its cache
    row); ``valid`` [B] is how many of the window's tokens are real (valid-
    prefix; 0 = dead slot).  Runs the deployed packed path so admission cost
    is O(prompt_len / chunk) launches at batched arithmetic intensity
    instead of O(prompt_len) batch-1 decode steps (DESIGN.md §12).
    Returns (last-valid-token logits [B, vocab], new caches).
    """
    qmode = quant_mode_for(cfg, "prefill_chunk")

    def prefill_chunk_step(params, caches, batch, index, valid,
                           block_tables=None):
        b, c = batch["tokens"].shape
        dec = dict(batch)
        idx = jnp.asarray(index, jnp.int32)
        vld = jnp.asarray(valid, jnp.int32)
        dec["positions"] = idx[:, None] + jnp.arange(c, dtype=jnp.int32)
        logits, _, caches = lm.forward(params, cfg, dec, quant_mode=qmode,
                                       caches=caches, cache_index=idx,
                                       cache_valid=vld,
                                       kv_shard_axis=kv_shard_axis,
                                       block_tables=block_tables)
        last = jnp.clip(vld - 1, 0, c - 1)
        return (jnp.take_along_axis(logits, last[:, None, None],
                                    axis=1)[:, 0], caches)

    return prefill_chunk_step


def make_verify_chunk_step(cfg, *, kv_shard_axis: str | None = None):
    """Speculative-verify step: a prefill-chunk pass returning the FULL
    per-position logits window (DESIGN.md §19).

    Identical cache semantics to :func:`make_prefill_chunk_step` — the
    [B, w] window writes K/V at per-slot offsets ``index`` with
    valid-prefix gating ``valid`` — but returns ``logits [B, w, vocab]``
    instead of only the last valid row: window row ``j`` is the target
    distribution for the token at position ``index + j + 1``, exactly
    what accept/reject needs for every drafted token at once.  Chunked
    writes equal sequential writes (the PR 2 invariant), so positions
    past the accepted prefix hold stale K/V that attention masks (via
    ``cache_valid``-derived visibility) until a later pass overwrites
    them — speculative rollback is simply not advancing the slot
    position.
    """
    qmode = quant_mode_for(cfg, "prefill_chunk")

    def verify_chunk_step(params, caches, batch, index, valid,
                          block_tables=None):
        b, c = batch["tokens"].shape
        dec = dict(batch)
        idx = jnp.asarray(index, jnp.int32)
        vld = jnp.asarray(valid, jnp.int32)
        dec["positions"] = idx[:, None] + jnp.arange(c, dtype=jnp.int32)
        logits, _, caches = lm.forward(params, cfg, dec, quant_mode=qmode,
                                       caches=caches, cache_index=idx,
                                       cache_valid=vld,
                                       kv_shard_axis=kv_shard_axis,
                                       block_tables=block_tables)
        return logits, caches

    return verify_chunk_step


def make_draft_step(cfg, k: int, *, kv_shard_axis: str | None = None):
    """Draft ``k`` greedy tokens per slot in ONE device launch.

    ``cfg`` is the DRAFT model config (same checkpoint re-packed at
    ``draft_w_bits``, serve/speculative.draft_model_config).  The body
    unrolls ``k + 1`` single-token decode forwards (k is small and
    static): step ``i`` feeds token ``i`` of the chain (the slot's last
    committed token at i=0, then each argmax draft) at position
    ``index + i`` and writes its K/V row; steps ``0..k-1`` also argmax
    the next draft token.  The extra ``k``-th forward exists purely for
    its cache write — when every draft is accepted the next cycle needs
    the K/V of the last drafted token in the draft cache too.

    ``limit`` [B] caps per-slot drafting (``min(k, remaining - 1)``):
    step ``i`` writes its row iff ``i < limit + 1``, so draft-cache
    writes never exceed the slot's reserved extent.  Draft sampling is
    deliberately greedy (a delta proposal): the host-side rejection rule
    then needs only the TARGET distribution, keeping the draft launch
    RNG-free while the committed-token distribution still exactly
    matches target-only sampling (DESIGN.md §19).

    Returns (draft_tokens [B, k] int32, new draft caches); entries past
    ``limit`` are garbage the host ignores.
    """
    qmode = quant_mode_for(cfg, "decode")

    def draft_step(params, caches, batch, index, limit, block_tables=None):
        idx = jnp.asarray(index, jnp.int32)
        lim = jnp.asarray(limit, jnp.int32)
        tok = jnp.asarray(batch["tokens"][:, 0], jnp.int32)
        drafted = []
        for i in range(k + 1):
            dec = {"tokens": tok[:, None],
                   "positions": (idx + i)[:, None]}
            step_valid = (lim + 1 > i).astype(jnp.int32)
            logits, _, caches = lm.forward(
                params, cfg, dec, quant_mode=qmode, caches=caches,
                cache_index=idx + i, cache_valid=step_valid,
                kv_shard_axis=kv_shard_axis, block_tables=block_tables)
            if i < k:
                # vocab padding is already masked by forward's pad_bias,
                # so the argmax stays inside the real vocab
                tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                drafted.append(tok)
        return jnp.stack(drafted, axis=1), caches

    return draft_step


def jitted_serving_steps(cfg, *, kv_shard_axis: str | None = None,
                         mesh=None):
    """Jitted ``(decode_step, prefill_chunk_step)`` pair, memoized per
    (model config, TP axis, mesh device set).

    A replica fleet (serve/router.Router) builds N ``ServingEngine``
    instances over ONE model config; without memoization each engine
    creates fresh ``jax.jit`` wrappers and re-pays trace + compile N
    times for identical computations.  Sharing the wrapper lets
    layout-identical replicas (same config, same — or no — mesh) reuse
    one executable.  The mesh's device ids are part of the key because
    jit executables bake in device placement: replicas on disjoint
    device groups must NOT share a wrapper, or the first replica's
    trace-time ``activation_mesh`` would leak into the others.
    """
    key = None if mesh is None else (
        tuple(d.id for d in mesh.devices.flat),
        tuple(sorted(mesh.shape.items())))
    return _jitted_serving_steps(cfg, kv_shard_axis, key,
                                 ulppack_attention.enabled())


@functools.lru_cache(maxsize=None)
def _jitted_serving_steps(cfg, kv_shard_axis, _mesh_key, _fused):
    # caches (arg 1) are donated: every engine call site reassigns its
    # cache pytree from the step's return, so the old buffers are dead on
    # entry and XLA may update the ring in place (DESIGN.md §20).  _fused
    # keys the memo on the REPRO_FUSED_DECODE kill-switch, which is read
    # at trace time — without it a flipped env var would hit stale traces.
    return (jax.jit(make_decode_step(cfg, kv_shard_axis=kv_shard_axis),
                    donate_argnums=(1,), compiler_options=SERVING_XLA_OPTIONS),
            jax.jit(make_prefill_chunk_step(cfg,
                                            kv_shard_axis=kv_shard_axis),
                    donate_argnums=(1,), compiler_options=SERVING_XLA_OPTIONS))


def jitted_speculative_steps(cfg, draft_cfg, k: int, *,
                             kv_shard_axis: str | None = None, mesh=None):
    """Jitted ``(draft_step, verify_chunk_step)`` pair for speculative
    decoding (DESIGN.md §19), memoized like :func:`jitted_serving_steps`.

    The draft step is keyed by the DRAFT config and ``k`` (its unroll
    depth is baked into the trace); the verify step by the TARGET config
    — so a fleet of replicas sharing one (target, draft, k) triple
    compiles each exactly once, and an engine whose target config
    already has serving steps shares nothing incorrectly (the verify
    window width is dynamic per trace, like prefill chunks).
    """
    key = None if mesh is None else (
        tuple(d.id for d in mesh.devices.flat),
        tuple(sorted(mesh.shape.items())))
    fused = ulppack_attention.enabled()
    return (_jitted_draft_step(draft_cfg, k, kv_shard_axis, key, fused),
            _jitted_verify_step(cfg, kv_shard_axis, key, fused))


@functools.lru_cache(maxsize=None)
def _jitted_draft_step(cfg, k, kv_shard_axis, _mesh_key, _fused):
    return jax.jit(make_draft_step(cfg, k, kv_shard_axis=kv_shard_axis),
                   donate_argnums=(1,), compiler_options=SERVING_XLA_OPTIONS)


@functools.lru_cache(maxsize=None)
def _jitted_verify_step(cfg, kv_shard_axis, _mesh_key, _fused):
    return jax.jit(make_verify_chunk_step(cfg,
                                          kv_shard_axis=kv_shard_axis),
                   donate_argnums=(1,), compiler_options=SERVING_XLA_OPTIONS)
