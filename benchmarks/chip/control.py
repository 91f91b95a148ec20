"""The readings a cell's correctness limit is set from, on the chip.

    python3 benchmarks/chip/control.py --workload stablelm-1.6b.chat \\
        --seeds 1,2,3

For each seed, in one process: a run of the cell (cell.run_cell, tracing
off) with a window of the benchmark's ``run_seconds`` at the cell's own
load, then the float8 control put in the program's place on the same
sample of served requests and judged by the same check (``cell.check``
with ``control_cdt``).  Prints one ``READING`` line per seed: the
program's verdict and widest gap (the number ``correct`` compares), and
the control's.  The limit lies between the largest program reading and
the smallest control reading (PERF.md gives both).  The benchmark's own
runs never run the control.  Needs a TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (default: run_seconds)")
    args = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    import jax.numpy as jnp
    import run as run_lib
    enable_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 3
    import cell
    import harness

    bench = harness.load_benchmark(ROOT)
    files = harness.cell_files(bench, args.workload, ROOT)
    parts = cell.read_files(bench, files)
    seconds = args.seconds or bench["run_seconds"]
    clock = run_lib.CompileClock(jax)
    captured = {}
    check = cell.check

    def grab(records, *a, **kw):
        captured["records"] = records
        return check(records, *a, **kw)

    cell.check = grab
    for seed in (int(s) for s in args.seeds.split(",")):
        res = cell.run_cell(bench, files, seed=seed, seconds=seconds,
                            trace=False, t_start=time.perf_counter(),
                            clock=clock, log=run_lib.log,
                            trace_dir=str(ROOT / "bench-out" / "control"),
                            parts=parts)
        ctl_ok, ctl_checks = check(
            captured["records"], parts["view"], parts["mix"],
            parts["limits"], seed, run_lib.log,
            control_cdt=jnp.float8_e4m3fn)
        print("READING", json.dumps({
            "workload": args.workload, "seed": seed, "seconds": seconds,
            "correct": res["correct"], "checks": res["checks"],
            "control_correct": ctl_ok, "control_checks": ctl_checks,
            "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
