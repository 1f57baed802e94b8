"""The one traffic generator: open-loop arrivals and request lengths from
a traffic mix's data file (`bench/traffic/<mix>.json`).

Every run of a mix gets the same schedule: the same inter-arrival gaps
and the same prompt and output lengths, in the same order. Each set is
the stratified quantiles of its distribution ((i + 0.5) / n for i < n),
put in an order drawn from the mix's own `schedule_seed`. The run's
--seed draws only the prompts' token ids (and, elsewhere, the weights),
so two seeds do the same work on other numbers.

Keys of a mix file:

  arrivals  {"kind": "poisson", "rate": r}: r requests/s
  prompt, output  {"median": m, "sigma": s, "min": lo, "max": hi}:
            lognormal lengths, clipped to [lo, hi]
  schedule_seed  the order of the gaps and lengths
  mode      "fp16" or "fp8" (the engine's forced precision)
  warm_s    seconds of traffic before the measured window
  drain_s   seconds the run may go on after the window so that the
            requests due in it finish (0: stop at the window's end)
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Arrival:
    due_s: float           # offset from the start of the traffic
    prompt: list[int]
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lengths(spec: dict, n: int, order) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    vals = np.clip(np.rint(vals), spec["min"], spec["max"]).astype(int)
    return order.permutation(vals)


def generate(mix: dict, seed: int, duration_s: float, vocab: int,
             rate_scale: float = 1.0) -> list[Arrival]:
    """Arrivals over [0, duration_s), sorted by due time; `seed` draws
    the token ids alone."""
    if mix["arrivals"]["kind"] != "poisson":
        raise ValueError(f"unknown arrivals kind {mix['arrivals']['kind']!r}")
    order = np.random.default_rng(mix["schedule_seed"])
    rate = float(mix["arrivals"]["rate"]) * rate_scale
    n = int(round(rate * duration_s))
    # stratified exponential gaps in the schedule's order, scaled to fill
    # [0, duration_s)
    gaps = order.permutation(-np.log1p(-_quantiles(n)) / rate)
    dues = (np.cumsum(gaps) - gaps[0]) * (duration_s / gaps.sum())
    prompts = _lengths(mix["prompt"], n, order)
    outputs = _lengths(mix["output"], n, order)
    ids = np.random.default_rng(seed)
    return [Arrival(float(d), ids.integers(1, vocab, int(p)).tolist(), int(o))
            for d, p, o in zip(dues, prompts, outputs)]
