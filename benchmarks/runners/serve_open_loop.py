"""Runner ``serve_open_loop``: one ``RaggedInferenceEngine`` behind one
``ServingEngine`` on one chip, offered a mix's requests at the cell's fixed
rate, timed at the client.

Set-up (all of it counted in ``setup_s``): weights on the device from the
seed, the KV pool sized to the HBM they leave, every (lane bucket x
live-page bucket) program of the SplitFuse step the mix's lengths can
reach, the comparison with the plain reference, and the lead-in traffic.
"""

from __future__ import annotations

import gc
import math
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from benchmarks import harness, weights
from benchmarks.harness import say


# ----------------------------------------------------------------------
# set-up
def build_engine(cell, seed: int):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.ragged import (RaggedConfig,
                                                RaggedInferenceEngine,
                                                kv_blocks_for_bytes,
                                                kv_page_bytes)
    from deepspeed_tpu.ops import ragged_host
    from deepspeed_tpu.parallel import mesh as mesh_mod

    cfg, ecfg = cell.config, cell.config["engine"]
    mesh_mod.reset_topology()
    model = harness.find("architectures", cfg["architecture"]).build(
        cfg, cell.n_layers)
    dtype = jnp.dtype(cfg["serve_dtype"])
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = weights.make(shapes, seed, dtype, cell.n_layers)
    n_params = sum(int(np.prod(s.shape))
                   for s in jax.tree_util.tree_leaves(shapes))
    rcfg = dict(token_budget=ecfg["token_budget"], max_seqs=ecfg["max_seqs"],
                kv_block_size=ecfg["kv_block_size"],
                max_context=ecfg["max_context"], dtype=dtype,
                enable_prefix_cache=ecfg["enable_prefix_cache"])
    n_blocks = ecfg["max_kv_blocks"]
    stats = jax.devices()[0].memory_stats()
    if stats and "bytes_limit" in stats:  # the HBM the weights left
        left = stats["bytes_limit"] - stats["bytes_in_use"] \
            - ecfg["kv_reserve_bytes"]
        n_blocks = min(n_blocks, kv_blocks_for_bytes(
            left, model.config, RaggedConfig(**rcfg)))
    if n_blocks < ecfg["max_seqs"]:
        raise harness.BenchError(f"only {n_blocks} KV pages fit")
    engine = RaggedInferenceEngine(
        model, RaggedConfig(n_kv_blocks=n_blocks, **rcfg), params=params)
    page = kv_page_bytes(model.config, engine.config)
    say(f"model: {cfg['architecture']} {cell.n_layers} layers, {n_params} "
        f"parameters in {dtype.name}; KV pool {n_blocks} pages of "
        f"{ecfg['kv_block_size']} tokens = {n_blocks * page} bytes; paged "
        f"attention path {engine.attention_path!r}, host packer "
        f"{ragged_host.packer()!r}")
    return model, params, engine


def reachable_shapes(engine, mix) -> List[Tuple[int, int]]:
    """Every (lanes, live pages) pair of the step the mix can reach: lane
    buckets up to the budget, and page buckets from the shortest prompt to
    the longest context. A bucket of T lanes needs more scheduled tokens
    than the bucket below holds, and ``max_seqs`` sequences of at most
    ``pages`` pages cannot supply more than that many tokens."""
    blk = engine.config.kv_block_size
    pow2 = lambda n: 1 << max(0, math.ceil(math.log2(max(1, n))))
    lo = pow2(math.ceil(mix["prompt_tokens"]["min"] / blk))
    hi = min(pow2(math.ceil((mix["prompt_tokens"]["max"]
                             + mix["output_tokens"]["max"]) / blk)),
             engine.max_pages)
    pages = [p for p in (1 << i for i in range(20)) if lo <= p <= hi]
    out = []
    below = 0
    for lanes in engine._buckets:
        out += [(lanes, p) for p in pages
                if engine.config.max_seqs * p * blk > below]
        below = lanes
    return out


def warm(engine, shapes: List[Tuple[int, int]]) -> None:
    """Runs the engine's own jitted step once for each shape on an empty
    batch (every lane inactive: the writes land on the scratch page), with
    the arguments ``put`` builds, so ``put`` finds each program compiled.
    The engine has no warm-up of its own (PERF.md, open questions)."""
    import jax.numpy as jnp

    from deepspeed_tpu.ops.ragged_host import build_batch, fill_tables

    cfg = engine.config
    if engine._step_fn is None:
        engine._step_fn = engine._build_step()
    tables = fill_tables([], [], cfg.max_seqs, engine.max_pages)
    sel = np.zeros((cfg.max_seqs,), np.int32)
    for lanes, pages in shapes:
        tok, slot, pos, _ = build_batch([], [], [], lanes)
        logits, engine.kv_pool = engine._step_fn(
            engine.params, engine.kv_pool, jnp.asarray(tok),
            jnp.asarray(slot), jnp.asarray(pos), jnp.asarray(tables),
            jnp.asarray(sel), pages)
    np.asarray(logits)


# ----------------------------------------------------------------------
# correct: prefill, then decode through the cache, against the reference
def engine_logits(engine, prompts: List[List[int]], decode_steps: int,
                  uid0: int = 10 ** 9):
    """Each prompt prefilled (all in one ragged step), then ``decode_steps``
    greedy tokens each through the paged cache. Returns (tokens fed [n][*],
    logits [n, decode_steps + 1, vocab])."""
    uids = [uid0 + i for i in range(len(prompts))]
    rows = engine.put(uids, prompts)
    while np.isnan(rows[:, 0]).any():            # a prompt split over steps
        todo = [i for i in range(len(uids)) if np.isnan(rows[i, 0])]
        more = engine.put([uids[i] for i in todo], [[] for _ in todo])
        for i, r in zip(todo, more):
            rows[i] = r
    fed = [list(p) for p in prompts]
    got = [rows.copy()]
    for _ in range(decode_steps):
        nxt = np.argmax(rows, -1)
        for f, t in zip(fed, nxt):
            f.append(int(t))
        rows = engine.put(uids, [[int(t)] for t in nxt])
        got.append(rows.copy())
    engine.flush(uids)
    if engine.prefix_cache is not None:
        engine.prefix_cache.drop_all(engine.allocator)
    return fed, np.stack(got, 1)


def position_errors(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Relative L2 error of each compared position's logits."""
    got = got.reshape(-1, got.shape[-1]).astype(np.float64)
    want = want.reshape(-1, want.shape[-1]).astype(np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def reference_logits(cell, params, fed: List[List[int]], prompt_lens,
                     decode_steps: int, quant: Optional[str] = None):
    import jax.numpy as jnp

    ref = harness.find("reference", cell.config["architecture"])
    width = cell.spec["check"]["reference_tokens"]
    tokens = np.zeros((len(fed), width), np.int32)
    for i, f in enumerate(fed):
        tokens[i, :len(f)] = f
    rows = np.repeat(np.arange(len(fed)), decode_steps + 1)
    cols = np.concatenate([np.arange(n - 1, n + decode_steps)
                           for n in prompt_lens])
    out = ref.logits_at(params, jnp.asarray(tokens), jnp.asarray(rows),
                        jnp.asarray(cols), cell.config, cell.n_layers, quant)
    return np.asarray(out).reshape(len(fed), decode_steps + 1, -1)


def check_prompts(cell, seed: int) -> List[List[int]]:
    gen = harness.find("generators", cell.traffic["generator"])
    chk = cell.spec["check"]
    rng = np.random.default_rng([seed, 7])
    lens = [min(int(round(gen.quantile(cell.traffic["prompt_tokens"], u))),
                chk["max_prompt_tokens"])
            for u in rng.uniform(0.02, 0.98, chk["sequences"])]
    return [rng.integers(1, cell.config["vocab_size"], (n,)).tolist()
            for n in lens]


def check(cell, engine, params, seed: int) -> Dict[str, float]:
    """The numbers ``correct`` compares: the engine's logits after prefill
    and after each decode step against the reference's full forward."""
    chk = cell.spec["check"]
    prompts = check_prompts(cell, seed)
    fed, got = engine_logits(engine, prompts, chk["decode_steps"])
    want = reference_logits(cell, params, fed, [len(p) for p in prompts],
                            chk["decode_steps"])
    err = position_errors(got, want)
    say(f"check: {len(prompts)} sequences of {[len(p) for p in prompts]} "
        f"prompt tokens, {err.size} positions compared")
    return {"logit_err_median": float(np.median(err)),
            "logit_err_max": float(np.max(err))}


# ----------------------------------------------------------------------
# the traced engine: host spans and live contexts around every engine call
class TracedEngine:
    """Delegates to the engine; ``put`` and ``put_spec`` run under a
    ``jax.profiler.TraceAnnotation`` (so the span sits on the device
    trace's clock) and leave what the readers need: host clock, and each
    scheduled sequence's new tokens and context."""

    def __init__(self, engine):
        self._engine = engine
        self.calls: List[Dict[str, Any]] = []

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def _traced(self, name, *args):
        import jax

        eng = self._engine
        before = {u: s.seen for u, s in eng.seqs.items()}
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("put"):
            out = getattr(eng, name)(*args)
        t1 = time.perf_counter()
        seqs = [(s.seen - before.get(u, 0), s.seen)
                for u, s in eng.seqs.items() if s.seen > before.get(u, 0)]
        self.calls.append({"t0": t0, "t1": t1, "seqs": seqs,
                           "live_after": len(eng.seqs)})
        return out

    def put(self, uids, tokens):
        return self._traced("put", uids, tokens)

    def put_spec(self, uids, tokens, drafts):
        return self._traced("put_spec", uids, tokens, drafts)


# ----------------------------------------------------------------------
# the window
class Sent:
    """One offered request as the client saw it."""

    __slots__ = ("due", "sent", "times", "req", "n_prompt", "n_out")

    def __init__(self, due: float, n_prompt: int, n_out: int):
        self.due, self.n_prompt, self.n_out = due, n_prompt, n_out
        self.sent = math.nan
        self.times: List[float] = []
        self.req = None

    @property
    def done(self) -> bool:
        return len(self.times) >= self.n_out


def offer(server, arrivals, t0: float, seconds: float, grace: float,
          on_window_open=None, probe=None) -> List[Sent]:
    """Sends every arrival when it is due (never early), then waits for
    the requests due inside the window, at most ``grace`` seconds.
    ``probe`` is called at every send (pool occupancy is sampled there)."""
    sent: List[Sent] = []
    for a in arrivals:
        if probe:
            probe()
        if a.due >= 0 and on_window_open:
            _sleep_until(t0)
            on_window_open()
            on_window_open = None
        _sleep_until(t0 + a.due)
        s = Sent(a.due, len(a.prompt), a.max_new_tokens)
        s.sent = time.perf_counter() - t0
        s.req = server.submit(
            a.prompt, max_new_tokens=a.max_new_tokens,
            on_token=lambda tok, s=s: s.times.append(time.perf_counter()))
        sent.append(s)
    _sleep_until(t0 + seconds)
    inside = [s for s in sent if s.due >= 0]
    while time.perf_counter() < t0 + seconds + grace \
            and not all(s.done or s.req.is_terminal for s in inside):
        time.sleep(0.02)
    for s in sent:                    # times relative to the window's start
        s.times = [t - t0 for t in s.times]
    return sent


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


def outstanding(sent: List[Sent], t: float) -> int:
    return sum(1 for s in sent if s.sent <= t
               and (not s.done or s.times[s.n_out - 1] > t))


def reduce_window(sent: List[Sent], seconds: float, grace: float
                  ) -> Dict[str, Any]:
    """End-to-end numbers of the requests due inside the window. A request
    that was refused, failed or is unfinished counts in ``failed`` and
    enters the percentiles at the time the client gave up. ``serve_tok_s``
    is what reached the client inside the window over its length: every
    token stamped in [0, seconds), and a prompt's tokens at its first token's
    stamp, of any request (the lead-in's too), so a stall or a backlog
    lowers it."""
    inside = [s for s in sent if s.due >= 0]
    gave_up = seconds + grace
    ttft, gaps, failed = [], [], 0
    for s in inside:
        if s.done:
            ttft.append(s.times[0] - s.due)
            gaps += list(np.diff(s.times[: s.n_out]))
        else:
            failed += 1
            ttft.append((s.times[0] if s.times else gave_up) - s.due)
            gaps.append(gave_up - (s.times[-1] if s.times else s.due))
    delivered = sum(
        sum(1 for t in s.times[: s.n_out] if 0 <= t < seconds)
        + (s.n_prompt if s.times and 0 <= s.times[0] < seconds else 0)
        for s in sent)
    late = [s.sent - s.due for s in inside]
    out = {
        "attempted": len(inside), "failed": failed,
        "ttft_p50_ms": harness.median(ttft) * 1e3,
        "ttft_p95_ms": harness.percentile(ttft, 95) * 1e3,
        "itl_p50_ms": harness.median(gaps) * 1e3,
        "itl_p95_ms": harness.percentile(gaps, 95) * 1e3,
        "serve_tok_s": delivered / seconds,
        "outstanding_mid": outstanding(sent, seconds / 2),
        "outstanding_end": outstanding(sent, seconds),
    }
    say(f"window: {len(inside)} requests due, {failed} failed; ttft median "
        f"{out['ttft_p50_ms']:.3f} ms, 95th percentile "
        f"{out['ttft_p95_ms']:.3f} ms over {len(ttft)}; token gap median "
        f"{out['itl_p50_ms']:.3f} ms, 95th percentile "
        f"{out['itl_p95_ms']:.3f} ms over {len(gaps)}; {delivered} tokens "
        f"reached the client inside the window, {out['serve_tok_s']:.1f} a "
        f"second; outstanding at the middle {out['outstanding_mid']}, at the "
        f"end {out['outstanding_end']}; generator late p95 "
        f"{harness.percentile(late, 95) * 1e3:.3f} ms")
    out["late_s"] = late
    return out


class Served:
    """The cell's engine behind its server, warmed and checked: set-up done
    once, then any number of windows (``run`` measures one; the by-hand
    ``benchmarks/sweep.py`` many, in one process)."""

    def __init__(self, cell, seed: int, trace: bool, env):
        from deepspeed_tpu.serving import ServingEngine

        self.cell = cell
        _, params, self.engine = build_engine(cell, seed)
        shapes = reachable_shapes(self.engine, cell.traffic)
        t = time.perf_counter()
        warm(self.engine, shapes)
        say(f"warm-up: {len(shapes)} step programs in "
            f"{time.perf_counter() - t:.1f} s; {env.compiles.summary()}")
        numbers = check(cell, self.engine, params, seed)
        self.ok = all([harness.check_line(k, numbers[k], lim)
                       for k, lim in cell.spec["check"]["limits"].items()])
        self.ok &= harness.check_line(
            "paged_kernel_missing",
            float(self.engine.attention_path != "pallas"), 0)
        gc.collect()
        self.served = TracedEngine(self.engine) if trace else self.engine
        self.server = ServingEngine(self.served,
                                    dict(cell.config["engine"]["serving"]))
        self.gen = harness.find("generators", cell.traffic["generator"])
        self.grace = float(cell.traffic["grace_seconds"])

    def window(self, rate: float, seed: int, seconds: float, tracer=None,
               arrivals=None) -> Dict[str, Any]:
        """One lead-in and one measured window at ``rate``."""
        cell, engine = self.cell, self.engine
        if arrivals is None:
            arrivals = self.gen.generate(cell.traffic, rate, seconds, seed,
                                         cell.config["vocab_size"])
        t0 = time.perf_counter() + float(cell.traffic["lead_seconds"])
        setup_s = harness.process_age_s() + (t0 - time.perf_counter())
        free = [engine.allocator.free_blocks]
        sent = offer(self.server, arrivals, t0, seconds, self.grace,
                     on_window_open=tracer.start if tracer else None,
                     probe=lambda: free.append(engine.allocator.free_blocks))
        out = reduce_window(sent, seconds, self.grace)
        out.update(drained=self.server.drain(timeout=120), t0=t0, sent=sent,
                   setup_s=setup_s)
        say(f"rate {rate} req/s; fewest KV pages free at a send {min(free)} "
            f"of {engine.config.n_kv_blocks} (pages the prefix cache holds "
            f"count as taken); free after drain "
            f"{engine.allocator.free_blocks}")
        return out

    def reopen(self) -> None:
        """Between two windows of one process: ``drain`` closed the door."""
        if self.engine.prefix_cache is not None:
            self.engine.prefix_cache.drop_all(self.engine.allocator)
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
