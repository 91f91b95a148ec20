"""Sharding rules: parameter-path -> PartitionSpec (DP/FSDP/TP/EP/SP).

Scheme (DESIGN.md §6):
  * FSDP axes  = ('data',) or ('pod', 'data') (cfg.parallel.fsdp_over_pod):
    parameters and optimizer state shard their largest non-TP dim here
    (ZeRO-3); XLA all-gathers at use and reduce-scatters gradients.
  * TP axis    = 'model': Megatron column/row pairs; embedding & logits shard
    the (padded) vocab dim.
  * EP         : expert dim shards over 'model' when num_experts divides the
    axis (jamba 16e); otherwise experts are FSDP + TP-within-expert
    (mixtral 8e).
  * SP         : long_500k shards KV-cache sequence over 'data'.

Every rule is divisibility-guarded: an axis that does not divide the tensor
dim is dropped (replicated) rather than producing an invalid sharding — the
dry-run asserts the *important* dims did shard (see tests/test_sharding.py).
"""

from __future__ import annotations

import re

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def _guard(mesh: Mesh, shape, spec: P) -> P:
    """Drop axes that do not divide the corresponding dim."""
    out = []
    for dim, axis in zip(shape, tuple(spec) + (None,) * (len(shape)
                                                         - len(spec))):
        if axis is None:
            out.append(None)
        elif dim % _axis_size(mesh, axis) == 0:
            out.append(axis)
        elif isinstance(axis, (tuple, list)):
            # try a prefix of the compound axis
            kept = [a for a in axis if dim % _axis_size(mesh, (a,)) == 0]
            out.append(tuple(kept[:1]) if kept else None)
        else:
            out.append(None)
    return P(*out)


# ---------------------------------------------------------------------------
# Activation sharding hints.  The model code calls constrain() at the few
# places where SPMD propagation needs help (post-embedding, logits, MoE
# dispatch); outside a mesh context it is a no-op so single-host tests and
# examples run unchanged.
# ---------------------------------------------------------------------------

_ACTIVE_MESH: list = [None]


class activation_mesh:
    """Context manager announcing the physical mesh to constrain()."""

    def __init__(self, mesh):
        self.mesh = mesh

    def __enter__(self):
        _ACTIVE_MESH.append(self.mesh)
        return self.mesh

    def __exit__(self, *exc):
        _ACTIVE_MESH.pop()


def kernel_mesh():
    """The active mesh when it spans several devices, else None.  GSPMD
    cannot partition a Mosaic custom call, so under such a mesh every
    Pallas kernel runs per device inside ``jax.shard_map``."""
    mesh = _ACTIVE_MESH[-1]
    return mesh if mesh is not None and mesh.size > 1 else None


def spec_on_mesh(mesh: Mesh, shape, *axes) -> P:
    """P(axes...) for an array of ``shape`` on ``mesh``: the 'dp' meta-axis
    resolves to the mesh's data axes, and axes that do not divide their
    dim drop (the array replicates along them)."""
    resolved = []
    for a in axes:
        if a == "dp":
            dp = tuple(ax for ax in ("pod", "data") if ax in mesh.shape)
            resolved.append(dp if dp else None)
        else:
            resolved.append(a)
    return _guard(mesh, shape, P(*resolved))


def constrain(x, *axes):
    """with_sharding_constraint(x, spec_on_mesh(mesh, x.shape, *axes));
    no-op without an active mesh."""
    mesh = _ACTIVE_MESH[-1]
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec_on_mesh(mesh, x.shape, *axes)))


def constrain_lanes(x, axis, groups):
    """Constrain the last axis of ``x``, ``groups`` equal blocks side by
    side (a lane-flat page-pool leaf holds one block per kv head), to
    ``axis`` where the axis divides ``groups``, so every shard holds whole
    blocks; else that axis replicates.  No-op without an active mesh."""
    mesh = _ACTIVE_MESH[-1]
    if mesh is None:
        return x
    lane = spec_on_mesh(mesh, (groups,), axis)[0]
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*([None] * (x.ndim - 1)), lane)))


def constrain_like_params(tree, cfg):
    """Constrain a param-shaped pytree (grads, accumulators) to the param
    sharding rules — keeps scan-carried gradient accumulators sharded instead
    of silently replicating (a multi-GB difference at jamba scale)."""
    mesh = _ACTIVE_MESH[-1]
    if mesh is None:
        return tree

    def one(path, leaf):
        spec = param_pspec(path_str(path), leaf, cfg, mesh)
        return jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(one, tree)


def path_str(path) -> str:
    parts = []
    for e in path:
        if hasattr(e, "key"):
            parts.append(str(e.key))
        elif hasattr(e, "idx"):
            parts.append(str(e.idx))
        else:
            parts.append(str(e))
    return "/".join(parts)


# (regex, spec factory(fsdp, tp, ep)) — first match wins.
_RULES = [
    # packed serving weights (same layout roles as their kernels)
    (r"(o|down|out_proj|ffn_down)/col_sums$", lambda f, t, e: P(None)),
    (r"col_sums$",               lambda f, t, e: P(t)),
    (r"(w_scale|a_scale|w_zp|a_zp)$", lambda f, t, e: P()),
    (r"lm_head/kernel$",         lambda f, t, e: P(f, t)),
    (r"frontend_proj/kernel$",   lambda f, t, e: P(None, f)),
    # MoE experts [E, din, dout]
    (r"moe/(up|gate)/kernel$",
     lambda f, t, e: P(t, f, None) if e else P(None, f, t)),
    (r"moe/down/kernel$",
     lambda f, t, e: P(t, None, f) if e else P(None, t, f)),
    (r"moe/(up|gate|down)/(w_step|a_step)$", lambda f, t, e: P()),
    (r"moe/router/kernel$",      lambda f, t, e: P(None, None)),
    # column-parallel projections [din, dout]
    (r"(attn|cross)/(q|k|v)/kernel$", lambda f, t, e: P(f, t)),
    (r"(attn|cross)/(q|k|v)/bias$",   lambda f, t, e: P(t)),
    (r"(mlp|moe)?/?(up|gate)/kernel$", lambda f, t, e: P(f, t)),
    (r"(in_proj|w_gates|ffn_up|up|gate|q|k|v)/kernel$",
     lambda f, t, e: P(f, t)),
    (r"(in_proj|w_gates|ffn_up|up|gate)/bias$", lambda f, t, e: P(t)),
    # row-parallel projections [dout_tp, d]
    (r"(o|down|out_proj|ffn_down)/kernel$", lambda f, t, e: P(t, f)),
    (r"(o|down|out_proj|ffn_down)/bias$",   lambda f, t, e: P(None)),
    # mamba internals
    (r"conv_w$",                 lambda f, t, e: P(None, t)),
    (r"(conv_b|D)$",             lambda f, t, e: P(t)),
    (r"A_log$",                  lambda f, t, e: P(t, None)),
    (r"x_proj/kernel$",          lambda f, t, e: P(t, None)),
    (r"dt_proj/kernel$",         lambda f, t, e: P(None, t)),
    (r"dt_proj/bias$",           lambda f, t, e: P(t)),
    # xLSTM gates
    (r"if_gate/kernel$",         lambda f, t, e: P(t, None)),
    (r"if_gate/bias$",           lambda f, t, e: P(None)),
    (r"r_gates$",                lambda f, t, e: P(None)),
    # norms / steps / scalars / cnn
    (r"(norm\w*|final_norm)/(scale|bias)$", lambda f, t, e: P(None)),
    (r"(w_step|a_step|alpha)$",  lambda f, t, e: P()),
    (r"(stem|layers/\d+)/kernel$", lambda f, t, e: P(None)),
    (r"head/kernel$",            lambda f, t, e: P(None, None)),
]


def param_pspec(path: str, leaf, cfg, mesh: Mesh) -> P:
    fsdp = (("pod", "data") if (cfg.parallel.fsdp_over_pod
                                and "pod" in mesh.shape) else ("data",))
    tp = "model"
    ep = cfg.parallel.expert_parallel and \
        cfg.num_experts > 0 and cfg.num_experts % mesh.shape[tp] == 0
    # packed weights take their kernel's rule
    path = re.sub(r"/w_packed$", "/kernel", path)
    # embedding: tied tables shard vocab over TP (logits matmul wants it);
    # untied tables shard d_model (gather-friendly, head handles logits)
    if re.search(r"embed/table$", path):
        spec = P(tp, None) if cfg.tie_embeddings else P(tp, fsdp)
        return _guard(mesh, np.shape(leaf), spec)
    for pat, fac in _RULES:
        if re.search(pat, path):
            spec = fac(fsdp, tp, ep)
            return _guard(mesh, np.shape(leaf), spec)
    # default: shard the largest dim over FSDP if divisible
    shape = np.shape(leaf)
    if not shape:
        return P()
    big = int(np.argmax(shape))
    spec = [None] * len(shape)
    spec[big] = fsdp
    return _guard(mesh, shape, P(*spec))


def param_shardings(params, cfg, mesh: Mesh):
    """Pytree of NamedSharding matching `params` (works on ShapeDtypeStructs
    as well as real arrays — used by the dry-run)."""
    def one(path, leaf):
        return NamedSharding(mesh, param_pspec(path_str(path), leaf, cfg,
                                               mesh))
    return jax.tree_util.tree_map_with_path(one, params)


def opt_state_shardings(opt_state, param_shardings_tree, cfg, mesh: Mesh):
    """Optimizer moments inherit the parameter sharding; 8-bit moment blocks
    ([nblocks, block] reshaped) fall back to FSDP on dim 0; counters
    replicate."""
    fsdp = (("pod", "data") if (cfg.parallel.fsdp_over_pod
                                and "pod" in mesh.shape) else ("data",))

    def one(path, leaf):
        ps = path_str(path)
        shape = np.shape(leaf)
        if ps.endswith("count") or not shape:
            return NamedSharding(mesh, P())
        if ps.endswith("/q") or ps.endswith("/scale"):
            return NamedSharding(mesh, _guard(mesh, shape,
                                              P(fsdp,
                                                *([None] * (len(shape) - 1)))))
        # fp32 moments: mirror the param rule by stripping the m/v prefix
        stripped = re.sub(r"^(m|v)/", "", ps)
        return NamedSharding(mesh, param_pspec(stripped, leaf, cfg, mesh))

    return jax.tree_util.tree_map_with_path(one, opt_state)


def batch_pspec(cfg, mesh: Mesh, global_batch: int) -> P:
    """Leading batch-dim sharding for inputs: ('pod','data') when divisible."""
    dp = [a for a in ("pod", "data") if a in mesh.shape]
    keep = []
    size = 1
    for a in dp:
        if global_batch % (size * mesh.shape[a]) == 0:
            keep.append(a)
            size *= mesh.shape[a]
    return P(tuple(keep) if keep else None)


def batch_shardings(batch, cfg, mesh: Mesh, global_batch: int):
    bp = batch_pspec(cfg, mesh, global_batch)

    def one(path, leaf):
        shape = np.shape(leaf)
        if not shape:
            return NamedSharding(mesh, P())
        if path_str(path).endswith("positions3"):  # [3, B, S]
            return NamedSharding(mesh, _guard(mesh, shape, P(None, *bp)))
        return NamedSharding(mesh, _guard(mesh, shape, bp))

    return jax.tree_util.tree_map_with_path(one, batch)


def cache_shardings(caches, cfg, mesh: Mesh, global_batch: int,
                    sequence_parallel: bool = False,
                    kv_head_shard: bool = False, paged: bool = False):
    """KV/state cache sharding.  decode_32k: batch over DP.  long_500k
    (batch=1): sequence over 'data' (SP) and head_dim over 'model'.

    ``kv_head_shard=True`` is the serving-TP layout (serve/shard.ShardPlan,
    DESIGN.md §15): attention K/V shard the kv-head axis (axis 2 of
    [B, S, KVH, hd]) over 'model' and the per-(pos, kv-head) scale planes
    [B, S, KVH] shard the same axis — valid for every storage precision
    cfg.quant.kv_bits selects, because quantization, word-packing and
    fused-dequant reads are all per-(pos, kv-head) local: a sub-byte
    cache's int32 words pack along head_dim *within* one kv head, so a
    head shard holds whole, locally-decodable words.  Head-dim sharding
    (the training default below) would instead split words across devices
    for packed caches and replicate the cache whenever kv_heads < axis
    size.

    ``paged=True`` (with ``kv_head_shard``) is the same layout over a page
    pool (DESIGN.md §18): the scale planes are ``[P, page_size, KVH]`` and
    the value leaves lane-flat ``[P, page_size, KVH * w]``
    (attention.init_paged_kv_cache), so the 'model' shard of the kv heads
    becomes a shard of the lane axis, taken only where 'model' divides
    KVH (each shard then holds whole heads; otherwise the leaf
    replicates, as a head axis that does not divide does).  The leading
    *page* axis replicates rather than taking the batch axis: pages are a
    shared physical resource every slot's block table may reference, not
    per-sequence rows."""
    bp = batch_pspec(cfg, mesh, global_batch)
    bp0 = bp[0] if len(bp) else None
    if paged:
        bp0 = None

    import os
    seq_shard = os.environ.get("REPRO_KV_SEQ_SHARD", "0") == "1"

    def one(path, leaf):
        ps = path_str(path)
        shape = np.shape(leaf)
        if leaf is None or not shape:
            return NamedSharding(mesh, P())
        if re.search(r"attn/(k_scale|v_scale)$", ps):
            if kv_head_shard:
                return NamedSharding(mesh, _guard(mesh, shape,
                                                  P(bp0, None, "model")))
            seq_ax = "model" if seq_shard else None
            return NamedSharding(mesh, _guard(mesh, shape,
                                              P(bp0, seq_ax, None)))
        if kv_head_shard and paged and re.search(r"attn/(k|v)$", ps):
            heads = _guard(mesh, (cfg.num_kv_heads,), P("model"))[0]
            return NamedSharding(mesh, P(None, None, heads))
        if kv_head_shard and re.search(r"attn/(k|v)$", ps):
            return NamedSharding(mesh, _guard(
                mesh, shape, P(bp0, None, "model", None)))
        if re.search(r"attn/(k|v)$", ps) or re.search(r"cross_kv", ps):
            if seq_shard:
                # canonical decode pattern: KV sharded over sequence,
                # q replicated over 'model'; softmax stats all-reduce.
                # head-dim sharding (the baseline) forces SPMD to replicate
                # the cache when kv_heads < axis size (§Perf cell C iter 3).
                seq_axes = ("data", "model") if sequence_parallel                     else "model"
                return NamedSharding(mesh, _guard(
                    mesh, shape, P(bp0, seq_axes, None, None)))
            if sequence_parallel:
                return NamedSharding(mesh, _guard(
                    mesh, shape, P(bp0, "data", None, "model")))
            return NamedSharding(mesh, _guard(
                mesh, shape, P(bp0, None, None, "model")))
        if ps.endswith("mamba/conv"):
            return NamedSharding(mesh, _guard(mesh, shape,
                                              P(bp0, None, "model")))
        if ps.endswith("mamba/ssm"):
            return NamedSharding(mesh, _guard(mesh, shape,
                                              P(bp0, "model", None)))
        if ps.endswith("mlstm/C"):
            return NamedSharding(mesh, _guard(mesh, shape,
                                              P(bp0, None, "model", None)))
        if ps.endswith("mlstm/n") or re.search(r"slstm/(c|n|h|m)$", ps):
            return NamedSharding(mesh, _guard(mesh, shape,
                                              P(bp0, None, "model")))
        if ps.endswith("mlstm/m"):
            return NamedSharding(mesh, _guard(mesh, shape, P(bp0, None)))
        return NamedSharding(mesh, _guard(mesh, shape, P(bp0)))

    return jax.tree_util.tree_map_with_path(
        one, caches, is_leaf=lambda x: x is None)
