#!/usr/bin/env python3
"""By hand, on the chip: a serving cell offered other rates, many windows
in one process (one set-up), to find the highest rate it sustains and how
far the tails move from seed to seed. The driver never runs this; the cell's
own rate is a number in ``benchmarks/workloads/<cell>.json``.

    python benchmarks/sweep.py <cell> <window> [<window> ...]

A window is ``rate:seed[:seconds[:iid]]`` (seconds defaults to 30). ``iid``
draws the mix independently instead of the generator's stratified draw:
exponential gaps and log-normal lengths from the seed, so the count in the
window varies too; it is there to set a true Poisson run beside the cell's.
Prints one ``sweep {...}`` JSON line a window, and at the end the (lanes,
pages) shapes of the step that the windows used, beside those warmed.
"""

from __future__ import annotations

import collections
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import harness, run  # noqa: E402
from benchmarks.harness import say  # noqa: E402

KEYS = ("ttft_p50_ms", "ttft_p95_ms", "itl_p50_ms", "itl_p95_ms",
        "serve_tok_s", "outstanding_mid", "outstanding_end")


def iid_arrivals(gen, mix, rate: float, seconds: float, seed: int, vocab: int):
    """A true Poisson process at ``rate`` from the lead-in's start to the
    window's end, every length drawn independently."""
    rng = np.random.default_rng([seed, 11])
    t, out = -float(mix.get("lead_seconds", 0)), []
    while True:
        t += rng.exponential(1.0 / rate)
        if t >= seconds:
            return out
        n, o = (int(round(gen.quantile(mix[k], rng.uniform())))
                for k in ("prompt_tokens", "output_tokens"))
        out.append(gen.Arrival(t, rng.integers(1, vocab, (n,)).tolist(), o))


def spy_on_shapes(engine) -> collections.Counter:
    """Counts the (lanes, live pages) of every call of the engine's step."""
    used: collections.Counter = collections.Counter()
    inner = engine._step_fn

    def step(params, pool, tokens, *rest):
        used[(int(tokens.shape[0]), int(rest[-1]))] += 1
        return inner(params, pool, tokens, *rest)

    engine._step_fn = step
    return used


def main(argv) -> int:
    from benchmarks.runners import serve_open_loop as r

    cell = harness.Cell(argv[0])
    dev, peaks = harness.require_device(cell.chips)
    harness.place_cache()
    env = run.Env(dev, peaks)
    windows = [w.split(":") for w in argv[1:]]
    s = r.Served(cell, int(windows[0][1]), False, env)
    warmed = r.reachable_shapes(s.engine, cell.traffic)
    used = spy_on_shapes(s.engine)
    mark = env.compiles.mark()
    for w in windows:
        rate, seed = float(w[0]), int(w[1])
        seconds = float(w[2]) if len(w) > 2 and w[2] else 30.0
        iid = len(w) > 3 and w[3] == "iid"
        arrivals = iid_arrivals(s.gen, cell.traffic, rate, seconds, seed,
                                cell.config["vocab_size"]) if iid else None
        out = s.window(rate, seed, seconds, arrivals=arrivals)
        say("sweep " + json.dumps({
            "rate": rate, "seed": seed, "seconds": seconds,
            "draw": "iid" if iid else "stratified", "due": out["attempted"],
            "failed": out["failed"], "drained": out["drained"],
            **{k: round(out[k], 3) for k in KEYS}}))
        s.reopen()
    s.server.close()
    say(f"compiled after the warm-up: {env.compiles.since(mark)}")
    say("shapes " + json.dumps({
        "warmed": len(warmed), "used": len(used),
        "not_warmed": sorted(k for k in used if k not in set(warmed)),
        "calls": sorted([list(k) + [n] for k, n in used.items()])}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
