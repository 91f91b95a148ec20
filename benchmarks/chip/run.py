"""One run of one benchmark cell on the chip.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Builds the cell's model from the seed (weights made on the device), warms
up the serving engine's step programs, serves the cell's traffic for
``--seconds``, follows the requests of the window to completion, checks
what was served against the plain reference (reference.py), and prints
one JSON object as the last line of stdout.  ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from a
profiler trace of the middle of the window.

It needs a TPU: without one (or with fewer chips than the cell asks for)
it exits 3 and prints no result.  The compile cache is ``.jax_cache`` in
the checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

def log(*a):
    print(*a, file=sys.stderr, flush=True)


class CompileClock:
    """JAX's own trace + lower + compile durations (persistent-cache loads
    included), and how many of them happened."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self.EVENTS:
            self.seconds += secs
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class PlanLog:
    """Every KernelPlan dispatched while tracing, by wrapping
    kernels/plan.dispatch; printed on stderr so each run names the
    backend of every op on its path."""

    def __init__(self, plan_lib):
        self.seen = {}
        orig = plan_lib.dispatch

        def dispatch(plan, *args, **kw):
            rows = args[0].shape[1] if plan.op == "attention_decode" else 0
            self.seen[(plan, rows)] = self.seen.get((plan, rows), 0) + 1
            return orig(plan, *args, **kw)

        plan_lib.dispatch = dispatch

    def report(self):
        for (plan, rows), n in sorted(self.seen.items(),
                                      key=lambda kv: str(kv[0][0])):
            d = dict(plan.describe(), interpret=plan.interpret, traces=n)
            if plan.op == "attention_decode":
                d["query_rows"] = rows
            log("plan", json.dumps(d, sort_keys=True, default=str))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro" / "serve" / "engine.py").is_file():
        log(f"run: no repro package under {ROOT / 'src'}; run from a "
            f"checkout of the repository")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import harness
    bench = harness.load_benchmark(ROOT)
    files = harness.cell_files(bench, args.workload, ROOT)

    import jax
    # the program's own rule: $JAX_COMPILATION_CACHE_DIR where the machine
    # sets it, else .jax_cache in the checkout (a fixed path: the path is
    # part of the cache's key)
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"run: needs a TPU; JAX found {dev.platform} "
            f"({len(devices)} device(s)); nothing was run")
        return 3
    if len(devices) < files["cell"]["chips"]:
        log(f"run: {args.workload} needs {files['cell']['chips']} chips, "
            f"JAX found {len(devices)}")
        return 3
    import cell as cell_lib
    clock = CompileClock(jax)
    from repro.kernels import plan as plan_lib
    plans = PlanLog(plan_lib)
    log(f"device_kind {dev.device_kind}, platform {dev.platform}, devices "
        f"{len(devices)}, jax {jax.__version__}, compile cache {cache}")
    result = cell_lib.run_cell(
        bench, files, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_start=T_START, clock=clock, log=log,
        trace_dir=str(ROOT / "bench-out" / "trace" / args.workload))
    plans.report()
    result["device"] = dict(platform=dev.platform, kind=dev.device_kind,
                            count=len(devices), **result["device"])
    checks = result.pop("checks")
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
