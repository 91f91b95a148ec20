"""Seeded traffic from a mix's parameter file (traffic/<mix>.json).

One generator serves every mix; a mix is data:

    {"arrival": "poisson" | "backlog",
     "rate_per_s": 0.8,              # poisson: offered requests per second
     "backlog": 160,                 # backlog: requests due at the start
     "prompt": {"median": 256, "sigma": 0.7, "min": 32, "max": 1024},
     "output": {"median": 128, "sigma": 0.7, "min": 16, "max": 512}}

Lengths are lognormal (median, sigma of the log), clipped to [min, max].
The schedule of sizes and arrivals is the mix's own: the stratified
quantiles of each distribution, in one fixed shuffled order (``ORDER_SEED``).
The run's seed draws the token ids (and,
elsewhere, the weights): a seed changes what is asked, never how much
work a run holds or when it arrives, so runs on different seeds spread no
wider than runs on one.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from statistics import NormalDist

import numpy as np

#: the one order of every mix's sizes and gaps; never the run's seed
ORDER_SEED = 0


@dataclasses.dataclass
class Planned:
    uid: int
    due_s: float          # offset from the window's start
    prompt: np.ndarray    # int32 token ids
    max_new_tokens: int


def load_mix(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    if mix["arrival"] not in ("poisson", "backlog"):
        raise ValueError(f"{path}: unknown arrival {mix['arrival']!r}")
    return mix


def count(mix: dict, seconds: float) -> int:
    """Requests in a window of ``seconds``."""
    if mix["arrival"] == "backlog":
        return int(mix["backlog"])
    return max(1, int(mix["rate_per_s"] * seconds))


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """n stratified lognormal draws (quantiles at (i + 0.5) / n), clipped
    and rounded to whole tokens, ascending."""
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    x = spec["median"] * np.exp(spec["sigma"] * np.asarray(z))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """n stratified exponential inter-arrival gaps of mean 1 / rate."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def schedule(mix: dict, seed: int, seconds: float,
             vocab_size: int) -> list[Planned]:
    """The run's requests, ordered by due time.  Poisson arrivals are
    scaled so that the last one falls inside the window."""
    n = count(mix, seconds)
    shape = np.random.default_rng(ORDER_SEED)
    prompts = shape.permutation(lognormal_quantiles(mix["prompt"], n))
    outputs = shape.permutation(lognormal_quantiles(mix["output"], n))
    if mix["arrival"] == "backlog":
        due = np.zeros(n)
    else:
        gaps = shape.permutation(exponential_gaps(mix["rate_per_s"], n))
        due = np.cumsum(gaps) - gaps[0]
        span = due[-1] + float(np.mean(gaps))
        if span > seconds:
            due = due * (seconds / span)
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                 (int(seed) >> 32) & 0xFFFFFFFF])
    return [Planned(uid=i, due_s=float(due[i]),
                    prompt=rng.integers(0, vocab_size, int(prompts[i]),
                                        dtype=np.int64).astype(np.int32),
                    max_new_tokens=int(outputs[i]))
            for i in range(n)]


def longest_request(mix: dict) -> int:
    """Prompt plus output tokens of the longest request the mix allows."""
    return int(mix["prompt"]["max"] + mix["output"]["max"])

