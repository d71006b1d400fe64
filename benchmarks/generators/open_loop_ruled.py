"""Open-loop request traffic whose schedule is fixed by rule for every
seed: ``generators/open_loop.py``'s stratified sets (the same prompt
lengths, output lengths and exponential-quantile gaps, the lead-in and the
window drawn apart), each list permuted by ``np.random.default_rng(
mix["order_seed"])`` and not by the run's seed. ``--seed`` draws the token
ids alone (and, in the runner, the weights), so every seed offers the same
requests at the same moments and runs differ by the system, not by how many
sequences an order happens to keep live at once (PERF.md, section 6: PR 35
and PR 42 were too noisy on cells whose tick follows the live count).

Mix parameters (``benchmarks/traffic/<mix>.json``): ``open_loop``'s, and
  order_seed: the one order every seed is offered
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from benchmarks.generators.open_loop import (Arrival, _gap_quantile,  # noqa: F401
                                             lengths, quantile)


def _part(mix, rate_per_s, start, span, order, ids, vocab_size):
    """``round(rate x span)`` arrivals inside [start, start + span): the
    distributions' quantiles, each list in the rule's order; the token ids
    from the seed."""
    n = max(1, int(round(rate_per_s * span)))
    gaps = np.array([_gap_quantile(mix["arrivals"], (i + 0.5) / n)
                     for i in range(n)])
    gaps = order.permutation(gaps * span / gaps.sum())   # they fill the span
    due = start + np.cumsum(gaps) - gaps[0]              # the first at its start
    prompts = order.permutation(lengths(mix["prompt_tokens"], n))
    outputs = order.permutation(lengths(mix["output_tokens"], n))
    return [Arrival(float(t), ids.integers(1, vocab_size, (int(p),)).tolist(),
                    int(o)) for t, p, o in zip(due, prompts, outputs)]


def generate(mix: Dict[str, Any], rate_per_s: float, seconds: float,
             seed: int, vocab_size: int) -> List[Arrival]:
    lead = float(mix.get("lead_seconds", 0))
    order = np.random.default_rng(int(mix["order_seed"]))
    ids = np.random.default_rng(seed)
    before = _part(mix, rate_per_s, -lead, lead, order, ids, vocab_size) \
        if lead > 0 else []
    return before + _part(mix, rate_per_s, 0.0, seconds, order, ids,
                          vocab_size)
