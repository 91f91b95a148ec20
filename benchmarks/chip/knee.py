"""Find a chat cell's knee on the chip: the highest offered rate whose
queue does not grow through a window.

    python3 benchmarks/chip/knee.py --config stablelm-1.6b \\
        --traffic chat-0.3rps --seconds 40 --rates 0.3,0.4,0.5

One process, one engine: first the mix as a backlog (every request due at
the start) gives the capacity in requests/s, then each rate runs the mix
open loop.  Each line printed is one window: requests offered, finished,
still queued at the close, and the mean wait for admission in the first
and last third of the window.  A rate whose queue at the close is near
empty and whose last third waits no longer than its first holds; the
cell runs at four fifths of the highest rate that holds.  Needs a TPU.
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


def window(engine, mix, seed, seconds, vocab):
    import harness
    import traffic
    plan = traffic.schedule(mix, seed, seconds, vocab)
    run = harness.drive(engine, plan, seconds,
                        backlog=mix["arrival"] == "backlog")
    recs = run["records"]
    t0, end = run["t0"], run["end"]
    third = seconds / 3

    def wait(lo, hi):
        w = [(r.admit if r.admit is not None else end) - r.due
             for r in recs if lo <= r.due - t0 < hi]
        return sum(w) / len(w) if w else 0.0

    finished_in = sum(1 for r in recs if r.done and r.tokens[-1] <= end)
    queued = sum(1 for r in recs if r.admit is None or r.admit > end)
    return {"offered": len(recs), "finished_in_window": finished_in,
            "queued_at_close": queued,
            "wait_first_third_s": wait(0, third),
            "wait_last_third_s": wait(2 * third, seconds + 1),
            "finished_per_s": finished_in / seconds,
            "tok_s": harness.end_to_end(run, seconds)["tok_s"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("knee: needs a TPU", file=sys.stderr)
        return 3
    import cell
    import harness
    import traffic
    import weights
    from repro.serve.engine import Metrics, ServingEngine

    view = harness.load_view(HERE / "configs" / f"{args.config}.json")
    mix = traffic.load_mix(HERE / "traffic" / f"{args.traffic}.json")
    w = weights.make_weights(view, args.seed)
    engine = ServingEngine(harness.program_config(view), w,
                           config=harness.engine_config(view, mix))
    del w
    cell.warm_up(engine, view, args.seed)
    vocab = view["vocab_size"]
    backlog = dict(mix, arrival="backlog",
                   backlog=int(4 * args.seconds))
    runs = [("backlog", backlog)] + [
        (float(r), dict(mix, rate_per_s=float(r)))
        for r in args.rates.split(",")]
    for i, (rate, m) in enumerate(runs):
        engine.metrics = Metrics()
        t = time.perf_counter()
        out = window(engine, m, args.seed + i, args.seconds, vocab)
        out.update(rate=rate, wall_s=time.perf_counter() - t)
        print("KNEE", json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
