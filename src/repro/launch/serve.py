"""CLI server: pack a model for deployment and serve synthetic requests
through the continuous-batching engine — or, with ``--data-parallel N``,
through the replica-fleet Router (serve/router.py, DESIGN.md §17).

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b \
        --reduced --requests 4 --prefill-chunk 16

Tensor-parallel serving (serve/shard.ShardPlan, DESIGN.md §15) on a
CPU-simulated mesh:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 PYTHONPATH=src \
        python -m repro.launch.serve --arch stablelm-1.6b --reduced \
        --model-parallel 4 --metrics

Replica fleet — a (data=2, model=2) mesh carved into two 2-way-TP
replica groups behind one load-balanced front door:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
        python -m repro.launch.serve --arch stablelm-1.6b --reduced \
        --data-parallel 2 --model-parallel 2 --metrics

``--trace-dir DIR`` records a profiler trace of the serving run (the
engine's host spans and the model's device scopes, serve/engine.py) into
DIR, for TensorBoard's profile plugin or ``jax.profiler.ProfileData``.

Flags are grouped (engine / sampling / quantization / parallelism /
fleet) and the engine side is derived through a single
``EngineConfig.from_args`` call, so the CLI and programmatic
construction cannot drift.
"""

from __future__ import annotations

import argparse
import contextlib
import json

import jax
import numpy as np

from repro import configs
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.serve.config import EngineConfig
from repro.serve.engine import Request, ServingEngine


def build_parser() -> argparse.ArgumentParser:
    """The serving CLI surface.  Exposed (not inlined in main) so tests
    can parse flag lists and assert EngineConfig.from_args consistency."""
    ap = argparse.ArgumentParser(
        description="Serve synthetic requests through the packed "
                    "continuous-batching engine or a replica fleet.")
    ap.add_argument("--arch", required=True, choices=configs.ALL_NAMES)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=6)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--trace-dir", default=None,
                    help="record a profiler trace of the serving run "
                         "(engine spans, device scopes) into this "
                         "directory")
    ap.add_argument("--metrics", action="store_true",
                    help="print the full metrics report (throughput split "
                         "by phase, occupancy, per-request TTFT and "
                         "time-per-output-token mean/p50/p95; fleet "
                         "aggregate + per-replica under --data-parallel) "
                         "plus the capacity/shard report as JSON")

    eng = ap.add_argument_group(
        "engine", "EngineConfig fields (serve/config.py) — consumed by "
                  "EngineConfig.from_args, the single construction path")
    eng.add_argument("--max-batch", type=int, default=2)
    eng.add_argument("--max-len", type=int, default=64)
    eng.add_argument("--prefill-chunk", type=int, default=16)
    eng.add_argument("--max-queue", type=int, default=0,
                     help="backpressure cap on queued requests per engine "
                          "(0 = none; under a fleet, a full replica queue "
                          "spills to the router)")
    eng.add_argument("--no-packed", action="store_true")
    eng.add_argument("--autotune", action="store_true",
                     help="warm-tune the serving kernel signatures missing "
                          "from the autotune cache before planning, then "
                          "persist the cache (tune once offline; plans "
                          "come back cache-backed on later launches)")
    eng.add_argument("--hbm-cache-budget-mb", type=float, default=0,
                     help="size batch slots from this HBM cache budget "
                          "(slots = budget // cache bytes per slot; with "
                          "--paged-kv, pages = budget // page bytes) "
                          "instead of --max-batch (0 = no budget)")
    eng.add_argument("--paged-kv", action="store_true",
                     help="paged KV cache: block-table indirection over a "
                          "refcounted page pool with prefix sharing and "
                          "copy-on-write (serve/pages.py, DESIGN.md §18); "
                          "the HBM budget then buys pages, --max-batch "
                          "bounds logical slots")
    eng.add_argument("--page-size", type=int, default=16,
                     help="token rows per KV page; must be a multiple of "
                          "the kv-bits word-packing tail (8 for 4-bit, 16 "
                          "for 2-bit)")
    eng.add_argument("--no-prefix-sharing", action="store_true",
                     help="disable radix prefix sharing across paged "
                          "requests (pages still allocated on demand)")
    eng.add_argument("--speculative-k", type=int, default=0,
                     help="speculative decoding (DESIGN.md §19): draft up "
                          "to K tokens per decode pass with a sub-byte "
                          "copy of the model, verify them in one target "
                          "call (0 = off)")
    eng.add_argument("--draft-w-bits", type=int, default=2,
                     choices=(1, 2, 3, 4),
                     help="draft model weight/activation precision (the "
                          "same checkpoint re-packed; only takes effect "
                          "on a packed engine)")
    eng.add_argument("--draft-kv-bits", type=int, default=-1,
                     choices=(-1, 0, 16, 8, 4, 2),
                     help="draft KV-cache precision override (-1 = "
                          "inherit the target's kv_bits)")

    samp = ap.add_argument_group("sampling")
    samp.add_argument("--temperature", type=float, default=0.0,
                      help="0 = greedy")
    samp.add_argument("--top-k", type=int, default=0)

    quant = ap.add_argument_group("quantization")
    quant.add_argument("--kv-bits", type=int, default=-1,
                       choices=(-1, 0, 16, 8, 4, 2),
                       help="KV cache storage precision override: 0/16 = "
                            "bf16, 8 = int8, 4/2 = bit-dense packed words; "
                            "-1 keeps the arch config's value")

    par = ap.add_argument_group("parallelism")
    par.add_argument("--model-parallel", type=int, default=1,
                     help="tensor-parallel shards per replica: packed "
                          "weights column-parallel, KV cache sharded on "
                          "the kv-head axis (serve/shard.ShardPlan).  "
                          "Testable on CPU via XLA_FLAGS=--xla_force_"
                          "host_platform_device_count=N")

    fleet = ap.add_argument_group(
        "fleet", "replica fleet (serve/router.Router, DESIGN.md §17)")
    fleet.add_argument("--data-parallel", type=int, default=1,
                       help="replica count: serve over a ('data'=N, "
                            "'model'=M) mesh carved into N replica "
                            "groups behind one load-balanced router "
                            "(least-loaded placement, spillover, session "
                            "affinity, drain/restore)")
    return ap


def _traced(trace_dir):
    """A profiler trace into ``trace_dir`` around the serving run, or
    nothing without one."""
    if not trace_dir:
        return contextlib.nullcontext()
    return jax.profiler.trace(trace_dir)


def _fleet_main(args, cfg, params, econf: EngineConfig):
    from repro.launch.mesh import make_serving_mesh
    from repro.serve.router import Router

    mesh = make_serving_mesh(model=args.model_parallel,
                             data=args.data_parallel)
    router = Router(cfg, params, config=econf, mesh=mesh)
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        # alternate sessions so affinity pinning is visible in the report
        router.submit(
            rng.integers(0, cfg.vocab_size, args.prompt_len).astype(
                np.int32),
            max_new_tokens=args.max_new_tokens,
            session=f"session-{i % 2}")
    with _traced(args.trace_dir):
        done = router.run_to_completion()
    rep = router.metrics_report()
    rep["capacity"] = router.capacity_report()
    toks = sum(len(h.output) for h in done)
    fleet = rep["fleet"]
    print(f"{len(done)} requests, {toks} generated tokens across "
          f"{fleet['attached']} replicas (mesh {dict(mesh.shape)})")
    if args.metrics:
        print(json.dumps(rep, indent=2))
    else:
        print(f"fleet prefill {fleet['prefill_tok_s']} tok/s, "
              f"decode {fleet['decode_tok_s']} tok/s, "
              f"ttft p95 {fleet['ttft_s']['p95']}s, "
              f"spilled {fleet['spilled']} "
              f"(--metrics for the full report)")


def main():
    args = build_parser().parse_args()
    enable_compile_cache()

    cfg = configs.get_config(args.arch, reduced=args.reduced)
    if args.kv_bits >= 0:
        cfg = cfg.replace(quant=cfg.quant.replace(kv_bits=args.kv_bits))
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    econf = EngineConfig.from_args(args)

    if args.data_parallel > 1:
        _fleet_main(args, cfg, params, econf)
        if args.autotune:
            from repro.kernels import autotune as autotune_lib
            print(f"autotune cache saved to "
                  f"{autotune_lib.active_cache().save()}")
        return

    mesh = None
    if args.model_parallel > 1:
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(args.model_parallel)
    eng = ServingEngine(cfg, params, config=econf, mesh=mesh)
    if args.autotune:
        from repro.kernels import autotune as autotune_lib
        print(f"autotune cache saved to "
              f"{autotune_lib.active_cache().save()}")
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        eng.submit(Request(
            uid=i,
            prompt=rng.integers(0, cfg.vocab_size, args.prompt_len).astype(
                np.int32),
            max_new_tokens=args.max_new_tokens))
    with _traced(args.trace_dir):
        done = eng.run_to_completion()
    rep = eng.metrics.report()
    rep["capacity"] = eng.capacity_report()
    toks = sum(len(r.output) for r in done)
    shards = eng.shard_plan.model_shards if eng.shard_plan else 1
    print(f"{len(done)} requests, {toks} generated tokens"
          + (f" (model-parallel x{shards})" if shards > 1 else ""))
    if args.metrics:
        print(json.dumps(rep, indent=2))
    else:
        print(f"prefill {rep['prefill_tok_s']} tok/s, "
              f"decode {rep['decode_tok_s']} tok/s, "
              f"ttft p50 {rep['ttft_s']['p50']}s, "
              f"tpot p50 {rep['tpot_s']['p50']}s "
              f"(--metrics for the full report)")


if __name__ == "__main__":
    main()
