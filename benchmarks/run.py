"""Benchmark harness — one module per paper table/figure (DESIGN.md §7).

    PYTHONPATH=src python -m benchmarks.run [--quick] [--out DIR]
        [--autotune] [--update-baseline]

Emits ``name,us_per_call,derived`` style CSV blocks per benchmark plus the
aggregated roofline table from the dry-run reports, and persists each
benchmark's rows as ``BENCH_<key>.json`` under ``--out`` (the artifacts the
bench-smoke CI lane uploads and gates with benchmarks/compare.py).

``--autotune`` warm-tunes the benchmark kernel signatures missing from the
active autotune cache before running (winners persisted to
``reports/autotune_<device>.json`` — the tune-once-offline pass; the
nightly workflow runs it full-grid).  ``--update-baseline`` merges the
fresh BENCH_*.json payloads into ``reports/BENCH_baseline.json``, the
one-command refresh for the CI perf-regression gate (DESIGN.md §14).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BASELINE_PATH = os.path.join("reports", "BENCH_baseline.json")


def warm_tune(quick: bool) -> str:
    """Tune the bench kernel signatures missing from the active cache and
    persist it.  Shapes come from the bench modules themselves (fig4 conv,
    table2/serve linears) so a bench-shape change cannot silently desync
    the cache from the gate; --quick restricts to the CI-speed subset."""
    import jax.numpy as jnp

    from benchmarks import fig4_conv2d, serve_microbench, \
        table2_kernel_report
    from repro.core.packing import PackSpec
    from repro.kernels import autotune

    spec = PackSpec(2, 2, jnp.int16.dtype)
    cin, co, f = fig4_conv2d.CIN, fig4_conv2d.COUT, fig4_conv2d.FH
    cp = -(-cin // spec.n_pack)
    # fig4/table2 conv shapes: the full grid covers quick AND full
    # resolutions so one nightly pass refreshes every gated shape
    hws = (fig4_conv2d.QUICK_HW,) if quick \
        else (fig4_conv2d.QUICK_HW, fig4_conv2d.H)
    per = 32 // spec.w_bits
    for hw in hws:
        for store, cdim in (("lanes", cp), ("dense", -(-cin // per))):
            autotune.tune_packed_conv2d(
                (1, hw, hw, cp), (f, f, cdim, co), spec, padding="VALID",
                backend="pallas", weight_store=store,
                k_full=cin if store == "dense" else None)
        # lane-layout axis (PackSpec family sweep, DESIGN.md §16): tiles
        # per candidate land in the same cache via tune_packed_conv2d
        autotune.tune_conv2d_layout((1, hw, hw, cin), (f, f, cin, co),
                                    spec, padding="VALID", backend="pallas")
    # decode-shaped serving linears (pallas tile grid); full adds the
    # table2 decode linear
    shapes = [serve_microbench.TUNED_LINEAR_SHAPE, (8, 1024, 1024)]
    if not quick:
        shapes.append((table2_kernel_report.M, table2_kernel_report.K,
                       table2_kernel_report.N))
    for m, k, n in shapes:
        autotune.tune_packed_matmul(m, -(-k // spec.n_pack), n, spec,
                                    backend="pallas")
        autotune.tune_matmul_layout(m, k, n, spec, backend="pallas")
    if not quick:
        autotune.tune_attention_chunk(2, 64, 64, 4, 2, 64, kv_bits=4)
        autotune.tune_attention_chunk(2, 64, 64, 4, 2, 64, kv_bits=0)
    # fused decode-attention kv-split grid (DESIGN.md §20): both gated
    # serve_microbench.run_attention_decode shapes (paged + contiguous)
    ab, askv, ah, akvh, ahd, abits, aps = serve_microbench.ATTN_DECODE_SHAPE
    autotune.tune_attention_decode(ab, askv, ah, akvh, ahd, kv_bits=abits,
                                   page_size=aps, backend="xla")
    autotune.tune_attention_decode(ab, askv, ah, akvh, ahd, kv_bits=abits,
                                   backend="xla")
    if not quick:
        for bits in (0, 4):              # nightly full grid: float + 4-bit
            autotune.tune_attention_decode(ab, askv, ah, akvh, ahd,
                                           kv_bits=bits, page_size=aps,
                                           backend="xla")
            autotune.tune_attention_decode(ab, askv, ah, akvh, ahd,
                                           kv_bits=bits, backend="xla")
    return autotune.active_cache().save()


def update_baseline(out_dir: str, quick: bool, keys) -> str:
    """Merge the BENCH_*.json files under ``out_dir`` into the committed
    gate baseline (reports/BENCH_baseline.json); benches not re-run this
    invocation (--only) keep their previous baseline entries."""
    from benchmarks.common import BENCH_SCHEMA
    from benchmarks.compare import load_payloads

    fresh = load_payloads(out_dir)
    merged = {}
    if os.path.exists(BASELINE_PATH):
        try:
            merged = load_payloads(BASELINE_PATH)
        except (OSError, ValueError):
            merged = {}
    merged.update({k: v for k, v in fresh.items() if not keys or k in keys})
    os.makedirs(os.path.dirname(BASELINE_PATH), exist_ok=True)
    payload = {"schema": BENCH_SCHEMA, "quick": quick, "benches": merged}
    with open(BASELINE_PATH, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return BASELINE_PATH


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller shapes (CI-speed)")
    ap.add_argument("--only", default="",
                    help="comma-list: fig4,fig5,table2,roofline,serve")
    ap.add_argument("--out", default="bench-out",
                    help="directory for BENCH_<key>.json result files "
                         "(kept out of the repo root so stale artifacts "
                         "never shadow the bench-out/ CI uploads)")
    ap.add_argument("--autotune", action="store_true",
                    help="warm-tune the bench kernel signatures into the "
                         "persisted autotune cache before running")
    ap.add_argument("--update-baseline", action="store_true",
                    help=f"merge the fresh results into {BASELINE_PATH} "
                         "(the CI perf-regression gate baseline)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (fig4_conv2d, fig5_precision_sweep,
                            roofline_table, serve_microbench,
                            table2_kernel_report)
    from benchmarks.common import write_bench_json

    if args.autotune:
        print(f"# autotune cache saved to {warm_tune(args.quick)}")

    benches = [
        ("fig4_conv2d  [paper Fig.4: conv2d impl comparison]",
         "fig4", fig4_conv2d.run),
        ("fig5_precision_sweep  [paper Fig.5: (W,A) region + speedups]",
         "fig5", fig5_precision_sweep.run),
        ("table2_kernel_report  [paper Table II analogue: kernel report]",
         "table2", table2_kernel_report.run),
        ("serve_microbench  [packed serving linears + engine-level "
         "chunked-prefill vs token-at-a-time]",
         "serve", serve_microbench.run),
        ("roofline_table  [assignment: 40-cell dry-run aggregate]",
         "roofline", roofline_table.run),
    ]
    failures = 0
    ran = []
    for title, key, fn in benches:
        if only and key not in only:
            continue
        print(f"\n=== {title} ===")
        t0 = time.time()
        try:
            rows = fn(quick=args.quick)
            dt = time.time() - t0
            if rows:
                path = write_bench_json(
                    key, {"bench": key, "quick": args.quick,
                          "seconds": round(dt, 2), "rows": rows},
                    args.out)
                print(f"# wrote {path}")
                ran.append(key)
            print(f"# done in {dt:.1f}s")
        except Exception as e:  # keep the harness running
            failures += 1
            print(f"# FAILED: {type(e).__name__}: {e}")
    if args.update_baseline and ran:
        path = update_baseline(args.out, args.quick, set(ran))
        print(f"\n# gate baseline refreshed: {path}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
