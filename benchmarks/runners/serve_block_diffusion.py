"""Runner ``serve_block_diffusion``: ``serve_open_loop``'s engine, server,
generator and window for a model that generates by diffusion over blocks
(SDAR: a tick yields a request no token or a block's tokens together), with
the reduction and the comparison such a stream needs.

The reduction. A request's tokens are stamped as they reach ``on_token``;
token ``i`` belongs to block ``(n_prompt + i) // block_length``; an emission
is a block's tokens, timed at its last stamp. ``itl_p50_ms`` is the median,
over every token after a request's first emission, of (its emission's time
less the emission before it) / (the tokens in its emission): the time a
reader waits a token, as this stream delivers it. (``serve_open_loop``'s
median of the gaps between successive tokens is zero here: three gaps in
four are.) A request refused, failed or unfinished counts in ``failed`` and
enters at the time the client gave up, as in ``serve_open_loop``; ``ttft``,
``serve_tok_s``, the outstanding counts and ``late_s`` are as there.

The comparison (``check``), outside the window, through the engine and its
cache: sequences from the mix, prefilled (split over ticks where a prompt
outgrows the budget beside the others), then the block a prompt's tail
opens and two whole blocks each, every denoise pass and every commit as
served (folded into the next block's first pass). Compared: the logits of
every lane of every denoise pass against ``reference/<architecture>.py`` fed
the state the engine fed that pass (``logit_err_median``, ``logit_err_p75``,
``logit_err_max``: a median alone would pass with close to half the
positions wrong; the third quartile lies under the tenth to sixth of the
positions, masked ones all, whose routing falls the other way in bfloat16,
PERF.md section 6). A later block reads the earlier one through the cache, so
a wrong commit shows. And what each pass decided: the (position, token)
pairs the engine wrote into the stream against the reference's plain rule
(``decide``) on the logits that pass handed back, ``decided_mismatch``
passes of them unlike, limit 0: a choice of the lowest confidence, another
tie order or count, or a block's lanes read at another slot's rows shows
here, which no logit does. A pass whose rule rests on two confidences
closer than float32's rounding (``DECIDE_GAP``) is left out.

The weights are ``benchmarks/weights.py``'s from the seed, with one row
changed: the embedding of the mask id is zero (``contextual_mask_row``).
That file draws a unit-variance embedding, beside which the branches' sums
are small, so a masked position's hidden state would be the mask id's own
row whatever its context: every masked lane of a seed would decide the
same token and take the same 8 experts a layer, the decided tokens
likewise, and a pass would read a fifth to a third of the experts, how many
depending on the seed (PERF.md section 6, PR 45: 12 to 23 of 64 touched at
a small size, 58 to 62 with the row zero). A trained model fills a masked
position from its context, and its passes touch every expert; with the row
zero so do these, on every seed alike. The program and the reference read
the same tree.

Set-up (all of it counted in ``setup_s``) is ``serve_open_loop``'s; the step
is warmed through the engine's public ``warm_step``.

By hand, on the chip (the driver runs neither):

    python benchmarks/runners/serve_block_diffusion.py sweep <cell> rate:seed[:seconds] ...
    python benchmarks/runners/serve_block_diffusion.py probe <cell> <seed> ... [-- <seed> ...]

``sweep`` is ``benchmarks/sweep.py`` through this runner's ``Served``;
``probe`` is ``benchmarks/probe_correct.py``'s method: what ``check``
compares for many seeds in one process, the program beside the controls
(the reference in fp8, under the plain causal mask, and with the blocks
before the one under way never committed), each control's numbers printed
beside the cell's limits, and for every position of the program over
``FAR`` the reference's router margins there beside all positions'; the
seeds after ``--`` get the program's numbers only.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402
from benchmarks.harness import say  # noqa: E402
from benchmarks.runners.serve_open_loop import (Sent, TracedEngine,  # noqa: E402,F401
                                                build_engine, check_prompts,
                                                offer, outstanding,
                                                position_errors,
                                                reachable_shapes)

CONTROLS = ("fp8", "causal", "stale")
DECIDE_GAP = 1e-5   # relative: two float32 confidences closer are a tie
FAR = 0.3           # a position's error that the median does not explain


# ----------------------------------------------------------------------
# the model's generation settings, as the configuration file states them
def block_length(cell) -> int:
    return int(cell.config["assumed"]["block_length"])


def mask_id(cell) -> int:
    return int(cell.config["assumed"]["mask_token_id"])


def without_mask_id(tokens: List[int], mask: int) -> List[int]:
    """A prompt never holds the id that marks an undecided position: where
    the generator drew it, the id below takes its place."""
    return [t - 1 if t == mask else t for t in tokens]


def arrivals_of(cell, gen, rate: float, seconds: float, seed: int):
    out = gen.generate(cell.traffic, rate, seconds, seed,
                       cell.config["vocab_size"])
    for a in out:
        a.prompt = without_mask_id(a.prompt, mask_id(cell))
    return out


# ----------------------------------------------------------------------
# set-up
def contextual_mask_row(engine, mask: int):
    """The engine's weights with the mask id's embedding row zero (the
    module docstring says why), written where the row lies; returns the
    tree, which the reference reads too."""
    import jax

    params = dict(engine.params)
    params["tok_embed"] = jax.jit(lambda e: e.at[mask].set(0),
                                  donate_argnums=0)(params["tok_embed"])
    engine.params = params
    return params


def warm(engine, shapes) -> None:
    """Every (lanes, live pages) program of the step, through the engine's
    own warm-up, so ``put`` finds each compiled."""
    for lanes, pages in shapes:
        engine.warm_step(lanes, pages)


# ----------------------------------------------------------------------
# correct: every denoise pass through the cache, against the reference
def engine_passes(engine, prompts: List[List[int]], whole_blocks: int,
                  mask: int, uid0: int = 10 ** 9) -> List[Dict[str, Any]]:
    """Each prompt prefilled and then generated through the block its tail
    opens and ``whole_blocks`` more, as served. Returns one entry a denoise
    pass: ``seq``, ``state`` (the sequence as that pass was fed it),
    ``logits`` [block, vocab] at the block under way, ``decided`` [(lane,
    token)] that the engine wrote into the stream after it, and ``before``:
    the generated blocks before it, each as it stood at its own last
    denoise pass (the prompt's whole blocks in front; None for a sequence's
    first block)."""
    B = engine.block_length
    uids = [uid0 + i for i in range(len(prompts))]
    for u, p in zip(uids, prompts):
        tail = len(p) % B
        engine.limit_stream(u, len(p) - tail + B * (whole_blocks + bool(tail)))
    out: List[Dict[str, Any]] = []
    last: Dict[int, List[int]] = {}     # seq -> its block's state, last fed
    prev: Dict[int, Optional[List[int]]] = {i: None for i in range(len(uids))}
    while True:
        live = [i for i, u in enumerate(uids)
                if u not in engine.seqs or engine.seqs[u].pending]
        if not live:
            break
        states = {}
        for i in live:
            seq = engine.seqs.get(uids[i])
            states[i] = list(seq.tokens) if seq is not None else \
                list(prompts[i]) + [mask] * (B - len(prompts[i]) % B)
        rows = engine._put_logits(
            [uids[i] for i in live],
            [prompts[i] if uids[i] not in engine.seqs else [] for i in live])
        for i, row in zip(live, rows):
            if np.isnan(row[0, 0]):
                continue
            state = states[i]
            if i in last and len(last[i]) != len(state):   # a new block
                prev[i] = (prev[i] or last[i][:-B]) + last[i][-B:]
            last[i] = state
            after = engine.seqs[uids[i]].tokens[len(state) - B:len(state)]
            out.append({"seq": i, "state": state, "logits": row.copy(),
                        "decided": [(n, int(t)) for n, t in enumerate(after)
                                    if state[len(state) - B + n] == mask
                                    and t != mask],
                        "before": prev[i], "prompt_tokens": len(prompts[i])})
    engine.flush(uids)
    return out


def reference_logits(cell, params, passes, control: Optional[str] = None):
    """The reference's logits [passes, block, vocab] at each pass's block,
    a row of its token matrix a pass; ``control``: one of ``CONTROLS``."""
    ref = harness.find("reference", cell.config["architecture"])
    B = block_length(cell)
    width = cell.spec["check"]["reference_tokens"]
    tokens = np.zeros((len(passes), width), np.int32)
    earlier = np.zeros_like(tokens)
    first = np.full((len(passes),), -1, np.int32)
    end = np.zeros((len(passes),), np.int32)
    for r, p in enumerate(passes):
        tokens[r, :len(p["state"])] = p["state"]
        if p["before"] is not None:
            earlier[r, :len(p["before"])] = p["before"]
            n = p["prompt_tokens"]
            first[r], end[r] = n - n % B, len(p["before"])
    rows = np.repeat(np.arange(len(passes)), B)
    cols = np.concatenate([np.arange(len(p["state"]) - B, len(p["state"]))
                           for p in passes])
    kw = {"fp8": dict(quant="fp8"), "int8": dict(quant="int8"),
          "causal": dict(mask="causal"),
          "stale": dict(stale=(earlier, first, end)),
          None: {}}[control]
    out = ref.logits_at(params, tokens, rows, cols, cell.config,
                        cell.n_layers, **kw)
    return np.asarray(out).reshape(len(passes), B, -1)


def check_passes(cell, engine, seed: int):
    chk = cell.spec["check"]
    prompts = [without_mask_id(p, mask_id(cell))
               for p in check_prompts(cell, seed)]
    passes = engine_passes(engine, prompts, int(chk["whole_blocks"]),
                           mask_id(cell))
    return prompts, passes


def error_numbers(err: np.ndarray) -> Dict[str, float]:
    return {"logit_err_median": float(np.median(err)),
            "logit_err_p75": float(np.percentile(err, 75)),
            "logit_err_p90": float(np.percentile(err, 90)),
            "logit_err_max": float(np.max(err))}


def decisions_unlike(cell, passes) -> Dict[str, int]:
    """Each pass's decisions, as the engine wrote them into the stream,
    against the reference's rule on the logits the pass handed back:
    ``compared`` passes (the rest rest on a tie, ``DECIDE_GAP``),
    ``unlike`` of them, and the tokens decided in all."""
    ref = harness.find("reference", cell.config["architecture"])
    B, mask = block_length(cell), mask_id(cell)
    n = B // int(cell.config["assumed"]["denoising_steps"])   # a pass
    compared = unlike = tokens = 0
    for p in passes:
        masked = np.asarray(p["state"][-B:]) == mask
        want, gap = ref.decide(p["logits"], masked, n, mask)
        tokens += len(p["decided"])
        if gap > DECIDE_GAP:
            compared += 1
            unlike += want != sorted(p["decided"])
    return {"compared": compared, "unlike": unlike, "tokens": tokens}


def check(cell, engine, params, seed: int) -> Dict[str, float]:
    """The numbers ``correct`` compares."""
    prompts, passes = check_passes(cell, engine, seed)
    got = np.stack([p["logits"] for p in passes])
    err = position_errors(got, reference_logits(cell, params, passes))
    rule = decisions_unlike(cell, passes)
    say(f"check: {len(prompts)} sequences of {[len(p) for p in prompts]} "
        f"prompt tokens, {len(passes)} denoise passes, {err.size} positions "
        f"compared, {int(np.sum(err > FAR))} of them over {FAR}; "
        f"{rule['tokens']} tokens decided, {rule['compared']} passes' "
        f"decisions held to the rule")
    out = {**error_numbers(err), "decided_mismatch": float(rule["unlike"])}
    say(f"check: 90th percentile {out['logit_err_p90']:.6g} (no limit: it "
        f"lies among the masked positions whose routing fell the other way)")
    return out


# ----------------------------------------------------------------------
# the window
def emissions(s: Sent, block: int) -> List[List[float]]:
    """[(time, tokens)] of a request's emissions: token ``i`` belongs to
    block ``(n_prompt + i) // block``, an emission is a block's tokens,
    timed at its last stamp."""
    out: List[List[float]] = []
    at = None
    for i, t in enumerate(s.times[: s.n_out]):
        b = (s.n_prompt + i) // block
        if b != at:
            out.append([t, 0])
            at = b
        out[-1][0] = t
        out[-1][1] += 1
    return out


def token_waits(s: Sent, block: int) -> List[float]:
    """For every token after the request's first emission: (its emission's
    time less the emission before it) / (the tokens in its emission)."""
    em = emissions(s, block)
    waits: List[float] = []
    for (t0, _), (t1, n) in zip(em, em[1:]):
        waits += [(t1 - t0) / n] * int(n)
    return waits


def reduce_window(sent: List[Sent], seconds: float, grace: float,
                  block: int) -> Dict[str, Any]:
    """End-to-end numbers of the requests due inside the window (the
    module docstring has the reduction)."""
    inside = [s for s in sent if s.due >= 0]
    gave_up = seconds + grace
    ttft, waits, failed = [], [], 0
    for s in inside:
        if s.done:
            ttft.append(s.times[0] - s.due)
            waits += token_waits(s, block)
        else:
            failed += 1
            ttft.append((s.times[0] if s.times else gave_up) - s.due)
            waits.append(gave_up - (s.times[-1] if s.times else s.due))
    delivered = sum(
        sum(1 for t in s.times[: s.n_out] if 0 <= t < seconds)
        + (s.n_prompt if s.times and 0 <= s.times[0] < seconds else 0)
        for s in sent)
    late = [s.sent - s.due for s in inside]
    out = {
        "attempted": len(inside), "failed": failed,
        "ttft_p50_ms": harness.median(ttft) * 1e3,
        "ttft_p95_ms": harness.percentile(ttft, 95) * 1e3,
        "itl_p50_ms": harness.median(waits) * 1e3,
        "itl_p95_ms": harness.percentile(waits, 95) * 1e3,
        "serve_tok_s": delivered / seconds,
        "outstanding_mid": outstanding(sent, seconds / 2),
        "outstanding_end": outstanding(sent, seconds),
    }
    say(f"window: {len(inside)} requests due, {failed} failed; ttft median "
        f"{out['ttft_p50_ms']:.3f} ms, 95th percentile "
        f"{out['ttft_p95_ms']:.3f} ms over {len(ttft)}; wait a token "
        f"(an emission's gap over its tokens) median "
        f"{out['itl_p50_ms']:.3f} ms, 95th percentile "
        f"{out['itl_p95_ms']:.3f} ms over {len(waits)}; {delivered} tokens "
        f"reached the client inside the window, {out['serve_tok_s']:.1f} a "
        f"second; outstanding at the middle {out['outstanding_mid']}, at the "
        f"end {out['outstanding_end']}; generator late p95 "
        f"{harness.percentile(late, 95) * 1e3:.3f} ms")
    out["late_s"] = late
    return out


class TracedBlocks(TracedEngine):
    """``TracedEngine`` for an engine whose ``seen`` moves over final K/V
    only (its record counts a sequence where ``seen`` moved, by as much: a
    denoise pass that commits nothing would be missing, and of the others
    the block under way). A call's ``seqs`` here are each scheduled
    sequence's lanes and the context they end at, read from the streams
    before and after the call: a pass that decided something ran from
    ``seen`` to the end of the stream as it was fed; any other (a prompt's
    chunk, a commit of its own) over what became final."""

    def _traced(self, name, uids, tokens):
        import jax

        eng = self._engine
        B, mask = eng.block_length, eng.model.config.mask_token_id
        fed = {}    # uid -> (seen, stream length, mask ids in its last block)
        for u, s in eng.seqs.items():
            fed[u] = (s.seen, len(s.tokens), s.tokens[-B:].count(mask))
        for u, t in zip(uids, tokens):
            if u not in fed:    # admitted by this call, its first block opened
                fed[u] = (0, len(t) - len(t) % B + B, B - len(t) % B)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("put"):
            out = getattr(eng, name)(uids, tokens)
        t1 = time.perf_counter()
        seqs = []
        for u, s in eng.seqs.items():
            seen, n, masks = fed[u]
            if s.tokens[n - B:n].count(mask) < masks:
                seqs.append((n - seen, n))
            elif s.seen > seen:
                seqs.append((s.seen - seen, s.seen))
        self.calls.append({"t0": t0, "t1": t1, "seqs": seqs,
                           "live_after": len(eng.seqs)})
        return out


class Served:
    """``serve_open_loop.Served`` for a block model: set-up once, then any
    number of windows."""

    def __init__(self, cell, seed: int, trace: bool, env, checked: bool = True):
        from deepspeed_tpu.serving import ServingEngine

        self.cell = cell
        self.model, _, self.engine = build_engine(cell, seed)
        self.params = contextual_mask_row(self.engine, mask_id(cell))
        if self.engine.block_length != block_length(cell):
            raise harness.BenchError(
                f"the engine generates by blocks of "
                f"{self.engine.block_length}, the configuration by "
                f"{block_length(cell)}")
        shapes = reachable_shapes(self.engine, cell.traffic)
        t = time.perf_counter()
        warm(self.engine, shapes)
        say(f"warm-up: {len(shapes)} step programs in "
            f"{time.perf_counter() - t:.1f} s; {env.compiles.summary()}")
        self.ok = True
        if checked:
            numbers = check(cell, self.engine, self.params, seed)
            self.ok = all([harness.check_line(k, numbers[k], lim) for k, lim
                           in cell.spec["check"]["limits"].items()])
        self.ok &= harness.check_line(
            "paged_kernel_missing",
            float(self.engine.attention_path != "pallas"), 0)
        gc.collect()
        self.served = TracedBlocks(self.engine) if trace else self.engine
        self.server = ServingEngine(self.served,
                                    dict(cell.config["engine"]["serving"]))
        self.gen = harness.find("generators", cell.traffic["generator"])
        self.grace = float(cell.traffic["grace_seconds"])

    def window(self, rate: float, seed: int, seconds: float, tracer=None,
               arrivals=None) -> Dict[str, Any]:
        """One lead-in and one measured window at ``rate``."""
        cell, engine = self.cell, self.engine
        if arrivals is None:
            arrivals = arrivals_of(cell, self.gen, rate, seconds, seed)
        t0 = time.perf_counter() + float(cell.traffic["lead_seconds"])
        setup_s = harness.process_age_s() + (t0 - time.perf_counter())
        free = [engine.allocator.free_blocks]
        sent = offer(self.server, arrivals, t0, seconds, self.grace,
                     on_window_open=tracer.start if tracer else None,
                     probe=lambda: free.append(engine.allocator.free_blocks))
        out = reduce_window(sent, seconds, self.grace, engine.block_length)
        out.update(drained=self.server.drain(timeout=120), t0=t0, sent=sent,
                   setup_s=setup_s)
        say(f"rate {rate} req/s; fewest KV pages free at a send {min(free)} "
            f"of {engine.config.n_kv_blocks}; free after drain "
            f"{engine.allocator.free_blocks}")
        return out

    def reopen(self) -> None:
        """Between two windows of one process: ``drain`` closed the door."""
        self.server.resume_admission()


def run(cell, seed: int, seconds: float, trace: bool, env) -> Dict[str, Any]:
    from deepspeed_tpu.inference.ragged import assert_block_balance

    s = Served(cell, seed, trace, env)
    tracer = env.tracer(min(float(cell.spec["trace_seconds"]), seconds)) \
        if trace else None
    wanted = {m["name"] for m in cell.metrics("end_to_end")}
    mark = env.compiles.mark()
    out = s.window(cell.spec["rate_per_s"], seed, seconds, tracer)
    s.server.close()
    ok = s.ok
    compiled = env.compiles.since(mark)
    for name, secs in compiled:
        say(f"FAULT: program {name!r} compiled inside the window "
            f"({secs:.2f} s): the warm-up missed a shape")
    ok &= harness.check_line("compiled_in_window", len(compiled), 0)
    ok &= harness.check_line("undrained", float(not out["drained"]), 0)
    if out["drained"]:
        assert_block_balance(s.engine)
    record = None
    if trace:
        record = {"tracer": tracer, "calls": s.served.calls,
                  "requests": [r for r in out["sent"] if r.due >= 0],
                  "late_s": out["late_s"], "t0": out["t0"],
                  "gave_up_s": seconds + s.grace,
                  "host_spans": ("put",), "gap_name": "between_puts",
                  "n_layers": cell.n_layers}
    return {"correct": bool(ok), "attempted": out["attempted"],
            "failed": out["failed"], "setup_s": out["setup_s"],
            "end_to_end": {k: v for k, v in out.items() if k in wanted},
            "record": record}


# ----------------------------------------------------------------------
# by hand
def sweep(cell, env, windows) -> None:
    from benchmarks import sweep as by_hand

    s = Served(cell, int(windows[0][1]), False, env, checked=False)
    warmed = reachable_shapes(s.engine, cell.traffic)
    used = by_hand.spy_on_shapes(s.engine)
    mark = env.compiles.mark()
    for w in windows:
        rate, seed = float(w[0]), int(w[1])
        seconds = float(w[2]) if len(w) > 2 and w[2] else 30.0
        out = s.window(rate, seed, seconds)
        say("sweep " + json.dumps({
            "rate": rate, "seed": seed, "seconds": seconds,
            "due": out["attempted"], "failed": out["failed"],
            "drained": out["drained"],
            **{k: round(out[k], 3) for k in by_hand.KEYS}}))
        s.reopen()
    s.server.close()
    say(f"compiled after the warm-up: {env.compiles.since(mark)}")
    say("shapes " + json.dumps({
        "warmed": len(warmed), "used": len(used),
        "not_warmed": sorted(k for k in used if k not in set(warmed)),
        "calls": sorted([list(k) + [n] for k, n in used.items()])}))


def probe(cell, seeds, program_only=()) -> None:
    import jax

    from benchmarks import weights

    ref = harness.find("reference", cell.config["architecture"])
    limits = cell.spec["check"]["limits"]
    model, params, engine = build_engine(cell, seeds[0])
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    B = block_length(cell)
    for seed in list(seeds) + list(program_only):
        engine.params = None
        del params
        gc.collect()
        engine.params = weights.make(shapes, seed, engine.config.dtype,
                                     cell.n_layers)
        params = contextual_mask_row(engine, mask_id(cell))
        prompts, passes = check_passes(cell, engine, seed)
        got = np.stack([p["logits"] for p in passes])
        want = reference_logits(cell, params, passes)
        e = position_errors(got, want)
        row = {"cell": cell.name, "seed": seed,
               "prompts": [len(p) for p in prompts], "passes": len(passes),
               "program": {**error_numbers(e), "over_far": int(np.sum(e > FAR)),
                           **decisions_unlike(cell, passes)}}
        if seed not in seeds:
            print(json.dumps(row), flush=True)
            continue
        for c in CONTROLS:
            d = position_errors(reference_logits(cell, params, passes, c),
                                want)
            row["control_" + c] = error_numbers(d)
            for k, v in row["control_" + c].items():
                if k in limits:
                    harness.check_line(f"control {c} {k}", v, limits[k])
            if c == "stale":    # it shows only to the passes that read one
                read = np.repeat([p["before"] is not None for p in passes], B)
                row["control_" + c]["median_where_read"] = \
                    float(np.median(d[read]))
        # why a position is far: each layer's router margin there, in the
        # reference, beside the margins of every compared position
        width = cell.spec["check"]["reference_tokens"]
        tokens = np.zeros((len(passes), width), np.int32)
        for r, p in enumerate(passes):
            tokens[r, :len(p["state"])] = p["state"]
        rows = np.repeat(np.arange(len(passes)), B)
        cols = np.concatenate([np.arange(len(p["state"]) - B, len(p["state"]))
                               for p in passes])
        m = ref.router_margins(params, tokens, rows, cols, cell.config,
                               cell.n_layers)
        least = m.min(-1)
        row["router_margin"] = {
            "least_a_position_quartiles": [
                float(q) for q in np.percentile(least, [25, 50, 75])],
            "far": [{"pass": int(i // B), "lane": int(i % B),
                     "masked": bool(passes[i // B]["state"][
                         len(passes[i // B]["state"]) - B + i % B]
                         == mask_id(cell)),
                     "err": float(e[i]), "least": float(least[i]),
                     "layer": int(m[i].argmin()),
                     "rank_of": [int(np.sum(least < least[i])), len(least)]}
                    for i in np.nonzero(e > FAR)[0]]}
        print(json.dumps(row), flush=True)


def main(argv) -> int:
    from benchmarks import run as bench_run

    what, cell = argv[0], harness.Cell(argv[1])
    dev, peaks = harness.require_device(cell.chips)
    harness.place_cache()
    if what == "sweep":
        sweep(cell, bench_run.Env(dev, peaks), [w.split(":") for w in argv[2:]])
    elif what == "probe":
        cut = argv.index("--") if "--" in argv else len(argv)
        probe(cell, [int(s) for s in argv[2:cut]],
              [int(s) for s in argv[cut + 1:]])
    else:
        raise SystemExit(f"sweep or probe, not {what!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
