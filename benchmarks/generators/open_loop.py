"""Open-loop request traffic from a mix's parameters: independent users
who send on a schedule whatever the server does.

The draw is stratified, not independent: every seed gets the same set of
prompt lengths, output lengths and gaps between arrivals (the quantiles of
the mix's distributions at evenly spaced points; for the gaps those of the
exponential distribution, rescaled to fill the span) inside the window, and
another such set in the lead-in, each in an order of its own, and its own
token ids. Two seeds offer the same work in another order, so runs differ by
the system and not by the draw. Against a true Poisson process the count in
a window does not vary and the longest gap is capped (PERF.md, section 4).

Mix parameters (``benchmarks/traffic/<mix>.json``):
  arrivals: {"process": "stratified_exponential"}
  prompt_tokens / output_tokens: {"dist": "lognormal", "median", "sigma",
      "min", "max"}
  lead_seconds: arrivals begin this long before the window opens

Another process or distribution is another generator, a file of its own.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


@dataclass
class Arrival:
    due: float                 # seconds from the window's start (< 0: lead)
    prompt: List[int]
    max_new_tokens: int


def quantile(dist: Dict[str, Any], u: float) -> float:
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    x = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
    return min(max(x, dist["min"]), dist["max"])


def _gap_quantile(arrivals: Dict[str, Any], u: float) -> float:
    """Gap between arrivals at unit rate."""
    if arrivals["process"] != "stratified_exponential":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    return -math.log1p(-u)


def lengths(dist: Dict[str, Any], n: int) -> np.ndarray:
    return np.array([int(round(quantile(dist, (i + 0.5) / n)))
                     for i in range(n)])


def _part(mix, rate_per_s, start, span, rng, vocab_size):
    """``round(rate x span)`` arrivals inside [start, start + span): the
    distributions' quantiles, each list in an order of the seed's own."""
    n = max(1, int(round(rate_per_s * span)))
    gaps = np.array([_gap_quantile(mix["arrivals"], (i + 0.5) / n)
                     for i in range(n)])
    gaps = rng.permutation(gaps * span / gaps.sum())   # they fill the span
    due = start + np.cumsum(gaps) - gaps[0]            # the first at its start
    prompts = rng.permutation(lengths(mix["prompt_tokens"], n))
    outputs = rng.permutation(lengths(mix["output_tokens"], n))
    out = []
    for t, p, o in zip(due, prompts, outputs):
        out.append(Arrival(float(t), rng.integers(
            1, vocab_size, (int(p),)).tolist(), int(o)))
    return out


def generate(mix: Dict[str, Any], rate_per_s: float, seconds: float,
             seed: int, vocab_size: int) -> List[Arrival]:
    """The lead-in and the window are drawn apart, so that the requests due
    inside the window are the same set for every seed."""
    lead = float(mix.get("lead_seconds", 0))
    rng = np.random.default_rng(seed)
    before = _part(mix, rate_per_s, -lead, lead, rng, vocab_size) \
        if lead > 0 else []
    return before + _part(mix, rate_per_s, 0.0, seconds, rng, vocab_size)
