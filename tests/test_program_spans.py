"""The program's host spans and device scopes (docs/observability.md
"Program spans and device scopes"), on the CPU.

One module fixture drives a tiny ragged engine behind a ``ServingEngine``
and a tiny trainer three times: under a ``jax.profiler`` session (the
``.xplane.pb`` must hold every span of the catalogue, nested as it says,
with integer attributes that agree with the engines' state), then twice with
no session: once with the seam in place and once with it taken out, which
must make the same device calls, read the clock as often and compile
nothing.
"""

import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dst
from deepspeed_tpu.inference import ragged as ragged_mod
from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
from deepspeed_tpu.models import Llama
from deepspeed_tpu.parallel import mesh as mesh_mod
from deepspeed_tpu.profiling import trace as seam
from deepspeed_tpu.runtime import engine as engine_mod
from deepspeed_tpu.serving import ServingEngine
from deepspeed_tpu.serving import server as server_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import program_trace, trace_reduce  # noqa: E402

#: the catalogue: span -> the span it nests in (None: a thread's outermost)
PARENT = {
    "ragged.put": "serve.put", "ragged.admit": "ragged.put",
    "ragged.pack": "ragged.put", "ragged.dispatch": "ragged.put",
    "ragged.fetch": "ragged.put", "ragged.rows": "ragged.put",
    "ragged.h2d": "ragged.dispatch", "ragged.call": "ragged.dispatch",
    "serve.tick": None, "serve.admit": "serve.tick",
    "serve.put": "serve.tick", "serve.emit": "serve.tick",
    "serve.retire": "serve.tick", "serve.wait": None,
    "train.step": None, "train.pre": "train.step",
    "train.dispatch": "train.step", "train.post": "train.step",
}
ATTRS = {
    "ragged.put": {"lanes", "pages", "seqs", "prefill", "decode", "free",
                   "q_tiles", "kv_steps", "passes", "kv_layers",
                   "write_tiles", "write_pages", "matched", "prompt"},
    "ragged.admit": {"matched", "prompt"}, "ragged.fetch": {"bytes"},
    "ragged.h2d": {"arrays", "bytes"}, "ragged.call": {"leaves"},
    "serve.tick": {"tick", "queued", "live"},
    "serve.admit": {"admitted", "preempted"}, "serve.put": {"retries"},
    "serve.emit": {"tokens"},
    # the step's number, and what the stage plans to move (PR 55)
    "train.step": {"step", "k", "plan_bytes"},
}
#: what ``train.step`` carries besides once the AOT step's collectives are
#: catalogued (``TrainEngine.warmup`` over more than one chip)
CATALOGUED = {"sent_bytes"}
#: the update's parts inside ``optimizer``, found by path as
#: ``readers/named_scope_device.py`` finds one (``zero_cast`` names nothing
#: in this float32 trainer's step: tests/test_zero_step_trace.py has it)
TRAIN_PATHS = (("optimizer", "norm"), ("optimizer", "update"))
VOCAB, MAX_SEQS, BLOCK, N_BLOCKS = 128, 4, 8, 64
PROMPTS = [40, 10, 5]          # 40 > the 32-token budget: a chunked prefill
NEW_TOKENS = 4


def _prompt(seed, n):
    return [int(t) for t in
            np.random.default_rng(seed).integers(1, VOCAB, n)]


class Counters:
    """Clock reads (every ``time`` function the package reads), step
    programs launched, and programs compiled, while installed."""

    CLOCKS = ("perf_counter", "time", "monotonic")

    def __init__(self):
        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._compiled)
        self._on = False

    def _compiled(self, event, seconds, **kw):
        if self._on and event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def counting(self, fn, field):
        def inner(*a, **k):
            if self._on:
                setattr(self, field, getattr(self, field) + 1)
            return fn(*a, **k)
        return inner

    def reset(self):
        self.clock_reads = self.device_calls = self.compiles = 0

    def snapshot(self):
        return (self.clock_reads, self.device_calls, self.compiles)


def serve(engine, start, tokens_out):
    """The serving workload: three requests together, then the longest
    prompt once more (its prefix is cached by then)."""
    srv = ServingEngine(engine, {"policy": "fcfs", "max_queue": 16},
                        start=start)

    def offer(prompts):
        reqs = [srv.submit(p, max_new_tokens=NEW_TOKENS,
                           on_token=tokens_out.append) for p in prompts]
        # a started server works on its own thread: wait up to a minute for
        # it (the suite's other workers share the host's cores, and the
        # traced run carries the profiler); by hand, 400 ticks are plenty
        for _ in range(6000 if start else 400):
            if all(r.is_terminal for r in reqs):
                return
            if start:
                time.sleep(0.01)
            else:
                srv._tick()
        raise AssertionError("the requests did not finish")

    first = [_prompt(i, n) for i, n in enumerate(PROMPTS)]
    offer(first)
    offer(first[:1])
    if start:
        time.sleep(0.05)               # idle polls: serve.wait
    srv.close()


def train(engine, batch):
    engine.train_batch(batch)
    engine.train_batch(batch)
    jax.block_until_ready(engine.train_steps([batch, batch])["loss"])


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    mesh_mod.reset_topology()
    model = Llama("tiny", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                  vocab_size=VOCAB, max_seq_len=256, use_flash=False,
                  remat=False)
    params = model.init(jax.random.PRNGKey(5))
    ragged = RaggedInferenceEngine(model, RaggedConfig(
        token_budget=32, max_seqs=MAX_SEQS, kv_block_size=BLOCK,
        n_kv_blocks=N_BLOCKS, max_context=128, dtype=jnp.float32,
        enable_prefix_cache=True), params=params)
    trainer, _, _, _ = dst.initialize(     # donates its own parameters
        model=model, params=model.init(jax.random.PRNGKey(6)),
        topology=mesh_mod.Topology.build_virtual({"data": 1}),
        config={"train_batch_size": 2, "steps_per_print": 1_000_000,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "gradient_clipping": 1.0})
    batch = {"input_ids": jnp.asarray(
        np.random.default_rng(0).integers(1, VOCAB, (2, 32)), jnp.int32)}

    # 1. under a profiler session (the first run also compiles everything)
    logdir = str(tmp_path_factory.mktemp("program_spans"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    traced_tokens = []
    step0 = trainer.global_steps
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        serve(ragged, True, traced_tokens)
        train(trainer, batch)
    finally:
        jax.profiler.stop_trace()
    trace = program_trace.load(trace_reduce.find_xplane(logdir))
    hlo = {"step": ragged._step_fn.lower(
        ragged.params, ragged.kv_pool, *(jnp.zeros((8,), jnp.int32),) * 3,
        jnp.zeros((MAX_SEQS, ragged.max_pages), jnp.int32),
        jnp.zeros((MAX_SEQS,), jnp.int32), 1).compile().as_text(),
        "train": trainer._train_step_fn.lower(
            trainer.params, trainer.opt_state, trainer.scaler_state,
            trainer.rng, batch).compile().as_text()}

    # 2. no session: the seam in place, then taken out of every module
    counters = Counters()
    ragged._step_fn = counters.counting(ragged._step_fn, "device_calls")
    trainer._train_step_fn = counters.counting(trainer._train_step_fn,
                                               "device_calls")
    for k, fn in list(trainer._train_steps_fns.items()):
        trainer._train_steps_fns[k] = counters.counting(fn, "device_calls")
    users = (ragged_mod, server_mod, engine_mod)
    real_clocks = {n: getattr(time, n) for n in Counters.CLOCKS}
    off = {}
    try:
        for n, fn in real_clocks.items():
            setattr(time, n, counters.counting(fn, "clock_reads"))
        counters._on = True
        for label, annotate in (("seam", seam.annotate),
                                ("without", lambda name, **a:
                                 seam._NoAnnotation())):
            for mod in users:
                mod.annotate = annotate
            counters.reset()
            tokens = []
            serve(ragged, False, tokens)
            train(trainer, batch)
            off[label] = counters.snapshot() + (len(tokens),)
        counters.reset()
        for i in range(100):           # the seam alone
            with seam.annotate("probe", lanes=i) as span:
                span.set_metadata(free=i)
        off["probe"] = counters.snapshot()
    finally:
        counters._on = False
        for n, fn in real_clocks.items():
            setattr(time, n, fn)
        for mod in users:
            mod.annotate = seam.annotate
    trainer.close()
    mesh_mod.reset_topology()
    return {"spans": trace.spans, "tokens": len(traced_tokens), "off": off,
            "hlo": hlo, "buckets": list(ragged._buckets),
            "max_pages": ragged.max_pages, "step0": step0,
            "leaves": len(jax.tree_util.tree_leaves((ragged.params,
                                                     ragged.kv_pool)))}


def named(runs, name):
    return [s for s in runs["spans"] if s.name == name]


@pytest.mark.parametrize("name", sorted(PARENT))
def test_span_is_recorded_and_nested(runs, name):
    spans = runs["spans"]
    mine = named(runs, name)
    assert mine, f"no {name} span in the trace"
    for s in mine:
        parent = spans[s.parent].name if s.parent is not None else None
        assert parent == PARENT[name], (name, parent)
        if s.parent is not None:
            assert spans[s.parent].start <= s.start \
                and s.end <= spans[s.parent].end
        assert set(s.attrs) == ATTRS.get(name, set()), (name, s.attrs)
        assert all(type(v) is int for v in s.attrs.values())


def test_put_attributes_agree_with_the_engine(runs):
    puts = named(runs, "ragged.put")
    for s in puts:
        a = s.attrs
        assert a["lanes"] in runs["buckets"]
        assert a["pages"] <= runs["max_pages"] \
            and a["pages"] & (a["pages"] - 1) == 0
        assert 1 <= a["seqs"] <= MAX_SEQS
        assert a["prefill"] + a["decode"] <= a["lanes"]
        assert 0 <= a["free"] <= N_BLOCKS
        # a tile a sequence at least, and a chunk a tile at least; far
        # fewer steps than a grid over every lane and the page bucket
        assert a["seqs"] <= a["q_tiles"] <= a["kv_steps"] \
            <= a["lanes"] * a["pages"]
        # one pass over two layers that hold pages: two kernel calls a tick
        assert (a["passes"], a["kv_layers"]) == (1, 2)
        # off the TPU a scatter writes the rows: the writer serves nothing
        assert (a["write_tiles"], a["write_pages"]) == (0, 0)
    assert puts[0].attrs["prefill"] > 0
    decode_only = [s.attrs for s in puts if s.attrs["prefill"] == 0]
    assert decode_only and all(a["decode"] == a["seqs"] for a in decode_only)
    # the 40-token prompt took two ticks of a 32-lane budget
    assert sum(s.attrs["prefill"] for s in puts) >= sum(PROMPTS)
    # behind the server a tick brings back token ids, not logits rows
    for s in named(runs, "ragged.fetch"):
        assert s.attrs["bytes"] == MAX_SEQS * 4


def test_dispatch_is_cut_where_the_work_changes_kind(runs):
    """Every ``ragged.dispatch`` holds one ``ragged.h2d`` (the tick's five
    int32 arrays, three of a lane each, the tables, the selection row, sent
    as ONE packed buffer: ``arrays`` 1, ``bytes`` the five's) and then one
    ``ragged.call`` (the arrays the call flattens beside that buffer:
    parameters and pool), and nothing of any length beside them once the
    step is built."""
    spans = runs["spans"]
    for d in named(runs, "ragged.dispatch"):
        inside = [s for s in spans if s.parent is not None
                  and spans[s.parent] is d]
        assert [s.name for s in inside] == ["ragged.h2d", "ragged.call"]
        h2d, call = inside
        lanes = spans[d.parent].attrs["lanes"]
        assert h2d.attrs == {"arrays": 1, "bytes": 4 * (
            3 * lanes + MAX_SEQS * runs["max_pages"] + MAX_SEQS)}
        assert call.attrs == {"leaves": runs["leaves"]}
        assert h2d.end <= call.start
    assert runs["leaves"] > 5


@pytest.mark.parametrize("entry", ["put", "put_spec"])
def test_h2d_and_call_nest_under_dispatch_in_either_entry(entry, monkeypatch):
    """``put`` and ``put_spec`` alike, without a session: the two spans open
    and close inside ``ragged.dispatch``, in that order, with ``leaves``
    counted when the step was built (a count of ``(params, kv_pool)``) and
    ``bytes`` the five host arrays' in the one buffer sent (the verify
    step's selection is ``[max_seqs, k]``)."""
    model = Llama("tiny", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                  vocab_size=VOCAB, max_seq_len=256, use_flash=False,
                  remat=False)
    engine = RaggedInferenceEngine(model, RaggedConfig(
        token_budget=32, max_seqs=MAX_SEQS, kv_block_size=BLOCK,
        n_kv_blocks=N_BLOCKS, max_context=128, dtype=jnp.float32),
        params=model.init(jax.random.PRNGKey(5)))
    log = []

    class Span:
        def __init__(self, name, attrs):
            self.name, self.attrs = name, attrs

        def __enter__(self):
            log.append(("open", self.name, dict(self.attrs)))
            return self

        def __exit__(self, *a):
            log.append(("close", self.name, None))
            return False

        def set_metadata(self, **attrs):
            pass

    monkeypatch.setattr(ragged_mod, "annotate",
                        lambda name, **attrs: Span(name, attrs))
    assert engine._call_leaves == 0              # no step built yet
    rows = engine.put([1], [_prompt(1, 10)])
    if entry == "put_spec":
        del log[:]
        nxt = int(np.argmax(rows[0]))
        engine.put_spec([1], [[nxt]], [[3, 4]])
    events = [(kind, name) for kind, name, _ in log
              if name in ("ragged.dispatch", "ragged.h2d", "ragged.call")]
    assert events == [("open", "ragged.dispatch"), ("open", "ragged.h2d"),
                      ("close", "ragged.h2d"), ("open", "ragged.call"),
                      ("close", "ragged.call"), ("close", "ragged.dispatch")]
    attrs = {name: a for kind, name, a in log if kind == "open"}
    leaves = len(jax.tree_util.tree_leaves((engine.params, engine.kv_pool)))
    assert attrs["ragged.call"] == {"leaves": leaves} and leaves > 5
    lanes, k = engine._buckets[0], 4 if entry == "put_spec" else 1
    assert attrs["ragged.h2d"] == {"arrays": 1, "bytes": 4 * (
        3 * lanes + MAX_SEQS * engine.max_pages + MAX_SEQS * k)}
    assert all(type(v) is int for a in attrs.values() for v in a.values())


def test_admit_attributes_count_prompts_and_prefix_hits(runs):
    admits = named(runs, "ragged.admit")
    assert sum(s.attrs["prompt"] for s in admits) == sum(PROMPTS) + PROMPTS[0]
    matched = [s.attrs["matched"] for s in admits if s.attrs["matched"]]
    # the repeated prompt adopts its cached full blocks, and nothing else does
    assert len(matched) == 1 and matched[0] % BLOCK == 0 \
        and 0 < matched[0] < PROMPTS[0]


def test_serve_attributes_agree_with_the_server(runs):
    ticks = [s.attrs["tick"] for s in named(runs, "serve.tick")
             if any(c.name == "serve.put" and runs["spans"][c.parent] is s
                    for c in named(runs, "serve.put"))]
    assert ticks == sorted(set(ticks)) and ticks[0] >= 1
    assert sum(s.attrs["admitted"] for s in named(runs, "serve.admit")) \
        == len(PROMPTS) + 1
    assert all(s.attrs["preempted"] == 0 for s in named(runs, "serve.admit"))
    assert all(s.attrs["retries"] == 0 for s in named(runs, "serve.put"))
    assert sum(s.attrs["tokens"] for s in named(runs, "serve.emit")) \
        == runs["tokens"] == (len(PROMPTS) + 1) * NEW_TOKENS


def test_train_steps_carry_their_number(runs):
    steps = [(s.attrs["step"], s.attrs["k"]) for s in named(runs,
                                                            "train.step")]
    s0 = runs["step0"]
    assert steps == [(s0, 1), (s0 + 1, 1), (s0 + 2, 2)]


@pytest.mark.parametrize("program,scopes", [
    ("step", ("embed", "weights", "attn", "paged_attention", "ffn", "head")),
    ("train", ("embed", "attn", "ffn", "head", "optimizer")),
])
def test_device_scopes_are_in_the_program(runs, program, scopes):
    """Every scope names operations of the compiled program, in the forms
    the trace's reader knows (``/attn/``, ``jvp(head)``); in the train
    step also under ``transpose(``, which the reader counts as backward."""
    names = set(re.findall(r'op_name="([^"]+)"', runs["hlo"][program]))
    for scope in scopes:
        mine = [n for n in names if scope in
                program_trace.scope_of(n)[1].split("/")]
        assert mine, f"no operation under {scope!r}"
        if program == "train" and scope != "optimizer":
            assert any(program_trace.scope_of(n)[2] for n in mine), scope
    assert not any(program_trace.scope_of(n)[2] for n in names
                   if program_trace.scope_of(n)[0] == "optimizer")


@pytest.mark.parametrize("path", TRAIN_PATHS, ids="/".join)
def test_train_step_paths_are_in_the_program(runs, path):
    """The update's parts name operations of the compiled train step and
    count under ``optimizer`` as before."""
    from benchmarks.readers.named_scope_device import under

    names = set(re.findall(r'op_name="([^"]+)"', runs["hlo"]["train"]))
    mine = [n for n in names if under(n, path)]
    assert mine, f"no operation under {'/'.join(path)!r}"
    assert {program_trace.scope_of(n)[0] for n in mine} == {"optimizer"}


def test_train_step_of_a_catalogued_step_carries_its_totals(monkeypatch):
    """Over four chips with an AOT step, ``train.step`` carries exactly the
    table's attributes and the catalogue's one, plain ints; without the
    AOT program (``runs``' trainer) exactly the table's."""
    from deepspeed_tpu.runtime.dataloader import shard_batch

    mesh_mod.reset_topology()
    model = Llama("tiny", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                  vocab_size=VOCAB, max_seq_len=64, use_flash=False,
                  remat=False)
    trainer, _, _, _ = dst.initialize(
        model=model, params=model.init(jax.random.PRNGKey(6)),
        topology=mesh_mod.Topology.build_virtual({"data": 4}),
        config={"train_batch_size": 4, "steps_per_print": 1_000_000,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3}})
    batch = shard_batch({"input_ids": jnp.asarray(
        np.random.default_rng(0).integers(1, VOCAB, (4, 32)), jnp.int32)},
        trainer.topo)
    seen = []
    monkeypatch.setattr(
        engine_mod, "annotate",
        lambda name, **attrs: seen.append((name, attrs))
        or seam._NoAnnotation())
    try:
        assert trainer.warmup(batch)
        trainer.train_batch(batch)
    finally:
        trainer.close()
        mesh_mod.reset_topology()
    attrs = dict(seen)["train.step"]
    assert set(attrs) == ATTRS["train.step"] | CATALOGUED
    assert all(type(v) is int for v in attrs.values())
    assert attrs["sent_bytes"] > 0 and attrs["plan_bytes"] > 0


def test_without_a_session_the_seam_changes_nothing(runs):
    off = runs["off"]
    # clock reads, step programs launched, compiles, tokens delivered
    assert off["seam"] == off["without"]
    assert off["seam"][2] == 0 and off["seam"][1] > 0
    assert off["seam"][3] == (len(PROMPTS) + 1) * NEW_TOKENS
    assert off["probe"] == (0, 0, 0)


# ----------------------------------------------------------------------
# the row writer's counters: what ``write_kv_pages`` serves a layer
@pytest.mark.parametrize("passes", [1, 4], ids=["one_pass", "four_passes"])
def test_put_counts_the_tiles_and_pages_the_writer_serves(passes, monkeypatch,
                                                          tmp_path):
    """``ragged.put`` carries ``write_tiles`` and ``write_pages``: the live
    tiles the row writer serves in one layer's call and the page slabs it
    moves there (a layer's count, not times the passes or the layers:
    ``passes`` and ``kv_layers`` are on the span), equal to a count by hand
    of the packed batch, for a chunk tick, a tick that mixes a chunk's tail
    with a decode lane, and a decode tick; the registry's
    ``inference/kv_pages_written`` sums pages x ``kv_layers``. The engine
    as the TPU runs it (kernels in interpret mode, head_dim 128), with one
    pass and with a looped stack's four, and it decodes what the gather
    engine decodes."""
    from deepspeed_tpu.config import TelemetryConfig
    from deepspeed_tpu.telemetry import Telemetry, set_telemetry

    model = Llama("tiny", n_layers=2, d_model=256, n_heads=2, n_kv_heads=2,
                  vocab_size=VOCAB, max_seq_len=256, use_flash=False,
                  remat=False, **(dict(total_ut_steps=passes,
                                       sandwich_norm=True)
                                  if passes > 1 else {}))
    params = model.init(jax.random.PRNGKey(11))
    cfg = RaggedConfig(token_budget=64, max_seqs=MAX_SEQS, kv_block_size=16,
                       n_kv_blocks=24, max_context=128, dtype=jnp.float32)
    seen = []

    class Span:
        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

        def set_metadata(self, **attrs):
            seen.append(attrs)

    real = ragged_mod.annotate
    monkeypatch.setattr(
        ragged_mod, "annotate",
        lambda name, **attrs: Span() if name == "ragged.put"
        else real(name, **attrs))

    def drive(engine):
        """70 + 5 prompt tokens against 64 lanes, then two decode ticks."""
        rows = engine.put([1, 2], [_prompt(1, 70), _prompt(2, 5)])
        assert np.isnan(rows[0]).all() and np.isfinite(rows[1]).all()
        ids = [int(np.argmax(rows[1]))]
        rows = engine.put([1, 2], [[], ids[-1:]])
        for _ in range(2):
            ids += [int(t) for t in np.argmax(rows, -1)]
            rows = engine.put([1, 2], [ids[-2:-1], ids[-1:]])
        return ids

    want = drive(RaggedInferenceEngine(model, cfg, params=params))
    assert all((a["write_tiles"], a["write_pages"]) == (0, 0) for a in seen)
    del seen[:]
    monkeypatch.setenv("DST_RAGGED_FORCE_PALLAS", "interpret")
    tel = Telemetry(TelemetryConfig(enabled=True, output_dir=str(tmp_path),
                                    jsonl_path="", stall_detection=False))
    set_telemetry(tel)
    try:
        engine = RaggedInferenceEngine(model, cfg, params=params)
        assert engine._writes_pages
        counter = tel.registry.counter("inference/kv_pages_written")
        before = counter.value          # the registry outlives a pipeline
        assert drive(engine) == want
        written = counter.value - before
    finally:
        set_telemetry(None)
    counts = [(a["lanes"], a["write_tiles"], a["write_pages"]) for a in seen]
    # tiles of 16 lanes, pages of 16 tokens; the scheduler packs the short
    # prompt first. Tick 1: uid 2's 5 lanes at positions 0-4 (a tile, a
    # page), then 59 lanes of uid 1 at 0-58 in tiles of 11 + 16 + 16 + 16
    # rows: positions 0-10 (a page), 11-26, 27-42 and 43-58 (two pages
    # each, the first shared with the tile before): 8 slabs. Tick 2: uid
    # 2's token at 5, and uid 1's last 11 at 59-69, one tile over pages 3
    # and 4. Then a lane each, at 6 / 70 and 7 / 71
    assert counts == [(64, 5, 8), (64, 2, 3), (64, 2, 2), (64, 2, 2)], counts
    assert {(a["passes"], a["kv_layers"]) for a in seen} == {(passes, 2 * passes)}
    assert [a["q_tiles"] for a in seen] == [c[1] for c in counts]
    assert written == sum(c[2] for c in counts) * 2 * passes
