"""What the ZeRO step says it moves (docs/observability.md "Program spans and
device scopes"; docs/observability.md "What a stage moves"), on four virtual devices.

One module fixture builds a small ``Transformer`` trainer a ZeRO stage over
``data=4`` and warms each up, so each has an AOT step and a catalogue of its
collectives (``TrainEngine.step_collectives``); the tests hold the stage's
byte plan (``zero_plan``) to counts by hand, the catalogue to the leaves'
shapes, ``train.step``'s attributes to both, the comm ledger to the
catalogue, and the three scopes this file's PR brought to names only: the
compiled step without them is the same program.
"""

import contextlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dst
from deepspeed_tpu.comm.comm import configure_comms_logger, get_comms_logger
from deepspeed_tpu.models import Llama
from deepspeed_tpu.parallel import mesh as mesh_mod
from deepspeed_tpu.profiling import collectives as coll
from deepspeed_tpu.runtime.dataloader import shard_batch
from deepspeed_tpu.runtime.engine import TrainEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import program_trace, trace_reduce  # noqa: E402
from benchmarks.readers.named_scope_device import under  # noqa: E402

VOCAB, D, FF, LAYERS, HEADS, KV = 128, 64, 256, 2, 4, 2
#: parameters, by hand: embedding and untied head, the final norm, and a
#: layer's two norms, q and o (D x D), k and v (D x D * KV / HEADS), and
#: the three SwiGLU matrices
COUNT = 2 * VOCAB * D + D + LAYERS * (
    2 * D + 2 * D * D + 2 * D * (D * KV // HEADS) + 3 * D * FF)
CHIPS = 4
NEW_SCOPES = ("optimizer/norm", "optimizer/update", "zero_cast")
#: kinds that bring parameters together / that reduce gradients
GATHERS, REDUCTIONS = ("all-gather",), ("reduce-scatter", "all-reduce")
#: the plan a chip a step by hand, with G = 4 bytes a parameter, P the same
#: and P_compute 2 (bfloat16): (gather_bytes, reduce_bytes)
BY_HAND = {
    0: (0, 2 * (COUNT * 4 * 3 // 4)),
    1: (COUNT * 4 * 3 // 4, COUNT * 4 * 3 // 4),
    2: (COUNT * 4 * 3 // 4, COUNT * 4 * 3 // 4),
    3: (2 * (COUNT * 2 * 3 // 4), COUNT * 4 * 3 // 4),
}


def _model():
    return Llama("tiny", n_layers=LAYERS, d_model=D, n_heads=HEADS,
                 n_kv_heads=KV, d_ff=FF, vocab_size=VOCAB, max_seq_len=64,
                 use_flash=False, remat=True)


def _engine(stage, chips=CHIPS, model=1):
    mesh_mod.reset_topology()
    topology = mesh_mod.Topology.build_virtual({"data": chips, "model": model})
    model = _model()
    engine, _, _, _ = dst.initialize(
        model=model, params=model.init(jax.random.PRNGKey(3)),
        topology=topology,
        config={"train_batch_size": 8, "steps_per_print": 1_000_000,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "gradient_clipping": 1.0, "bf16": {"enabled": True},
                "zero_optimization": {
                    "stage": stage,
                    "stage3_param_persistence_threshold": 0}})
    batch = shard_batch({"input_ids": jnp.asarray(
        np.random.default_rng(0).integers(1, VOCAB, (8, 32)), jnp.int32)},
        engine.topo)
    return engine, batch


@pytest.fixture(scope="module")
def engines():
    """stage -> (engine, batch) over four chips, each warmed up."""
    out = {}
    for stage in (0, 1, 2, 3):
        engine, batch = _engine(stage)
        assert engine.warmup(batch)
        out[stage] = (engine, batch)
    yield out
    for engine, _ in out.values():
        engine.close()
    mesh_mod.reset_topology()


def _leaf_bytes(engine):
    """Bytes a leaf, a layer of a stacked leaf, or one chip's shard of
    either can take in float32 or bfloat16."""
    sizes = set()
    for leaf in jax.tree_util.tree_leaves(engine.params):
        for n in (leaf.size, leaf.size // LAYERS):
            for cut in (1, CHIPS):
                sizes |= {n // cut * 2, n // cut * 4}
    return sizes


# ----------------------------------------------------------------------
@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_plan_is_the_stage_formula_by_hand(engines, stage):
    plan = engines[stage][0].zero_plan()
    gather, reduce = BY_HAND[stage]
    assert plan == {
        "chips": CHIPS, "zero_stage": stage, "grad_bytes": COUNT * 4,
        "param_bytes": {0: 0, 3: COUNT * 2}.get(stage, COUNT * 4),
        "gather_bytes": gather, "reduce_bytes": reduce,
        "plan_bytes": gather + reduce}
    assert all(type(v) is int for v in plan.values())


def test_plan_counts_a_chip_s_part_of_a_leaf_another_axis_cuts():
    """Under a ``model`` axis of 2 a chip gathers and reduces half of each
    leaf that axis cuts: the plan counts what one device's shards hold
    (every leaf is ZeRO-cut here, over ``data`` = 2), not whole leaves."""
    engine, _ = _engine(3, chips=2, model=2)
    try:
        parts = sum(leaf.addressable_shards[0].data.size * 2
                    for leaf in jax.tree_util.tree_leaves(engine.params))
        assert COUNT // 2 < parts < COUNT      # some leaves cut, not all
        plan = engine.zero_plan()
        assert (plan["chips"], plan["grad_bytes"], plan["param_bytes"]) \
            == (2, parts * 4, parts * 2)
        assert plan["plan_bytes"] == 2 * (parts * 2 // 2) + parts * 4 // 2
    finally:
        engine.close()
        mesh_mod.reset_topology()


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_catalogue_lists_what_the_compiled_step_holds(engines, stage):
    """Every entry is an instruction of the AOT program's text with a kind,
    bytes and a replica group of the four chips; the reductions of stage 0
    and 1 are the whole float32 gradient tree once; a sharded stage gathers,
    and its gathers (at stage 3 its reductions too, a layer at a time) carry
    the leaves' sizes."""
    engine, _ = engines[stage]
    found = engine.step_collectives()
    text = engine._train_step_aot.as_text()
    assert found and all(isinstance(c, coll.Collective) for c in found)
    assert len({c.name for c in found}) == len(found)
    for c in found:
        assert re.search(r"%?" + re.escape(c.name) + r" = ", text), c.name
        assert c.kind in set(coll.KINDS.values()) and c.bytes >= 0
        assert c.members == tuple(range(CHIPS)) and c.group == CHIPS
        assert engine._axis_of(c.members) == "data"
        assert c.runs >= 1 and c.runs_known
        assert c.in_loop == ("/while/body/" in c.op_name) or not c.op_name
    reductions = [c for c in found if c.kind in REDUCTIONS]
    gathers = [c for c in found if c.kind in GATHERS]
    assert reductions
    sizes = _leaf_bytes(engine)
    if stage <= 1:
        # the whole float32 gradient tree all-reduced once (the compiler
        # lays leaves end to end in one instruction), a scalar or two more
        total = sum(c.bytes * c.runs for c in reductions)
        assert COUNT * 4 <= total <= COUNT * 4 + 64
    elif stage == 3:
        assert {c.bytes for c in reductions} & sizes
    if stage == 0:
        assert not gathers
    else:
        assert gathers and {c.bytes for c in gathers} & sizes
    # the scan over layers: what sits in its body runs once a layer
    assert {c.runs for c in found if c.in_loop} <= {LAYERS}
    attrs = engine._step_attrs
    assert attrs["sent_bytes"] >= 0.5 * attrs["plan_bytes"] > 0


@pytest.mark.parametrize("stage", [1, 2, 3])
def test_the_update_holds_no_collective_of_its_own(engines, stage):
    """A moment lies as its gradient does (``opt_state_shardings`` takes the
    model's specs), so the compiled step holds no ``all-to-all`` whose scope
    is under ``optimizer``. At stage 3 ``optimizer/update`` holds no
    collective at all and what the optimizer sends is the norm's scalars;
    at stages 1 and 2 it holds the stage's own gather of the updated
    parameters, one a leaf, and nothing else. On PR 55's tree the
    same assertion fails: with the moments cut by their shapes alone this
    step held 24 float32 ``all-to-all`` under ``optimizer`` at stage 3 and
    12 at stage 2 (on the chip, at Mistral-7B's widths, 24 ran of the 26 in
    the program: PERF.md section 6, PR 55 and PR 57).

    ``sent_bytes`` against the plan is held where the program is the
    chip's: ``tests/test_tpu_compile.py::
    test_zero3_update_sends_nothing_and_the_step_stays_under_its_plan``.
    The CPU's partitioner all-reduces activations where the TPU's gathers
    weights, so this step sends several times its plan whatever the
    optimizer state does."""
    engine, _ = engines[stage]
    found = engine.step_collectives()
    ours = [c for c in found if under(c.op_name, ["optimizer"])]
    assert [c for c in ours if c.kind == "all-to-all"] == []
    update = [c for c in found if under(c.op_name, ["optimizer", "update"])]
    if stage == 3:
        assert update == []
        assert ours and sum(c.sent_bytes * c.runs for c in ours) < 1024
    else:
        leaves = len(jax.tree_util.tree_leaves(engine.params))
        assert [c.kind for c in update] == ["all-gather"] * leaves
        assert sum(c.bytes for c in update) == COUNT * 4
    # g, mu, nu and, at stage 3 with a threshold of 0, the master: one spec
    specs = lambda tree: [s.spec for s in jax.tree_util.tree_leaves(tree)]
    # at stage 1 the gradients arrive whole; the moments are cut as at 2
    grads = specs(engines[max(stage, 2)][0].grad_shardings)
    for tree in (engine.opt_state_shardings.mu, engine.opt_state_shardings.nu,
                 *([engine.param_shardings] if stage == 3 else [])):
        assert specs(tree) == grads


def test_one_chip_has_an_empty_catalogue_and_a_plan_of_zero():
    engine, batch = _engine(3, chips=1)
    try:
        assert engine.warmup(batch)
        assert engine.step_collectives() == []
        plan = engine.zero_plan()
        assert (plan["chips"], plan["plan_bytes"], plan["gather_bytes"],
                plan["reduce_bytes"]) == (1, 0, 0, 0)
        assert engine._step_attrs == {"plan_bytes": 0}
    finally:
        engine.close()
        mesh_mod.reset_topology()


def test_without_an_aot_program_the_catalogue_is_silent(engines):
    """The lazy jit path has no program to read: nothing is catalogued,
    ``train.step`` carries the plan alone; and an AOT program that stops
    matching takes its catalogue with it."""
    engine, batch = _engine(3)
    try:
        assert engine.step_collectives() == []
        assert set(engine._step_attrs) == {"plan_bytes"}
        assert engine.warmup(batch)
        assert engine.step_collectives()
        assert engine._step_attrs == engines[3][0]._step_attrs
        engine._forget_aot()
        assert engine.step_collectives() == []
        assert set(engine._step_attrs) == {"plan_bytes"}
    finally:
        engine.close()
        mesh_mod.reset_topology()


# ----------------------------------------------------------------------
# the three scopes: in the program by name, and nothing but names
def _op_names(text):
    return set(re.findall(r'op_name="([^"]+)"', text))


def test_the_new_scopes_are_in_the_lowered_and_the_compiled_step(engines):
    """As the readers find a scope: adjacent components of an ``op_name``,
    each by its last word (a transform may wrap one: ``jvp(zero_cast)``)."""
    engine, batch = engines[3]
    lowered = set(re.findall(r'loc\("(jit\([^"]+)"', engine._train_step_fn.lower(
        engine.params, engine.opt_state, engine.scaler_state, engine.rng,
        batch).as_text(debug_info=True)))
    names = _op_names(engine._train_step_aot.as_text())
    has = lambda paths, scope: any(under(n, scope.split("/")) for n in paths)
    # each names arithmetic, so each is in the compiled step too (a scope
    # around a sharding constraint alone names no instruction there: the
    # constraints onto ``param_shardings`` and ``grad_shardings`` have none)
    for scope in NEW_SCOPES:
        assert has(lowered, scope) and has(names, scope), scope
    # first words: ``optimizer`` is a scope of the readers' fixed list,
    # ``zero_cast`` is not, so its operations count where they counted
    # before: outside ``optimizer``, forward or backward by ``transpose(``
    # alone
    for n in names | lowered:
        top, key, _ = program_trace.scope_of(n)
        if under(n, ["zero_cast"]):
            assert top != "optimizer", n
        if under(n, ["optimizer"]):
            assert top == key == "optimizer", n
    assert any(program_trace.scope_of(n)[2] for n in names
               if under(n, ["zero_cast"]))        # the gradients' way back


def _strip(text):
    """An HLO module's text without what names carry: each instruction's
    metadata, and the tables of files, functions and stack frames that the
    metadata points into (between the module's line and its first
    computation)."""
    head, _, rest = text.partition("\n")
    first = re.search(r"^(ENTRY )?%[\w.\-]+ \(", rest, re.M)
    return head + "\n" + re.sub(r",? ?metadata=\{[^}]*\}", "",
                                rest[first.start():])


def test_without_the_scopes_the_compiled_step_is_the_same_program(
        engines, monkeypatch):
    """The same build with the three new names patched out compiles to the
    same optimized HLO, metadata aside: names only."""
    engine, batch = engines[3]
    with_scopes = engine._train_step_aot.as_text()
    real = jax.named_scope
    dropped = {s.split("/")[-1] for s in NEW_SCOPES}
    monkeypatch.setattr(
        jax, "named_scope",
        lambda name: contextlib.nullcontext() if name in dropped
        else real(name))
    monkeypatch.setattr(TrainEngine, "_compute_copy",
                        TrainEngine._compute_copy.__wrapped__)
    bare, _ = _engine(3)
    try:
        assert bare.warmup(batch)
        without = bare._train_step_aot.as_text()
    finally:
        bare.close()
    names = _op_names(without)
    assert not any(under(n, s.split("/")) for n in names for s in NEW_SCOPES)
    assert any(under(n, ["optimizer"]) for n in names)
    assert _strip(with_scopes) == _strip(without)
    assert with_scopes != without          # the names were there to strip


# ----------------------------------------------------------------------
def test_train_step_span_carries_the_plan_and_the_catalogue(engines,
                                                            tmp_path):
    """Under a profiler session ``train.step`` holds its number and the
    static attributes, plain ints equal to the engine's own."""
    engine, batch = engines[3]
    step0 = engine.global_steps
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        jax.block_until_ready(engine.train_batch(batch)["loss"])
        jax.block_until_ready(engine.train_steps([batch, batch])["loss"])
    finally:
        jax.profiler.stop_trace()
    spans = [s for s in program_trace.load(
        trace_reduce.find_xplane(str(tmp_path))).spans
        if s.name == "train.step"]
    assert [(s.attrs["step"], s.attrs["k"]) for s in spans] \
        == [(step0, 1), (step0 + 1, 2)]
    by = coll.totals(engine.step_collectives())
    want = {"plan_bytes": sum(BY_HAND[3]),
            "sent_bytes": sum(t["sent_bytes"] for t in by.values())}
    for s in spans:
        assert {k: v for k, v in s.attrs.items()
                if k not in ("step", "k")} == want
        assert all(type(v) is int for v in s.attrs.values())


@pytest.mark.parametrize("stage", [1, 3])
def test_comm_ledger_books_the_catalogue_and_no_guess_beside_it(engines,
                                                                stage):
    """With a catalogue the step's comm breakdown is what the compiled step
    holds, kind by kind (gathers too), every step the same; the synthetic
    ``reduce_scatter`` of the whole gradient tree is not booked beside it."""
    from deepspeed_tpu.telemetry.registry import get_registry

    engine, batch = engines[stage]
    log = get_comms_logger()
    was = log.enabled
    log.reset()
    configure_comms_logger(True)
    try:
        engine._comm_booked = None
        engine._comm_totals_prev = {}
        calls = get_registry().counter("comm/all_gather/calls")
        calls_before = calls.value
        want = {}
        for c in engine.step_collectives():
            e = want.setdefault(TrainEngine._COMM_OPS[c.kind],
                                {"count": 0.0, "bytes": 0.0})
            e["count"] += c.runs
            e["bytes"] += c.bytes * c.runs
        gathered = get_registry().counter("comm/all_gather/bytes")
        before = gathered.value
        for _ in range(2):
            engine.train_batch(batch)
            delta, comm_s = engine._comm_step_delta()
            assert set(delta) == set(want) and comm_s is None
            for op, e in want.items():
                assert delta[op]["count"] == e["count"]
                assert delta[op]["bytes"] == delta[op]["wire_bytes"] \
                    == e["bytes"]
        assert "all_gather" in want
        assert gathered.value - before == 2 * want["all_gather"]["bytes"]
        assert calls.value - calls_before == 2 * want["all_gather"]["count"]
        guess = engine.zero_plan()["grad_bytes"]
        assert guess not in log.records.get("reduce_scatter", {})
        # one record a (kind, payload), on the axis its group runs along
        sizes = {c.bytes for c in engine.step_collectives()
                 if c.kind == "all-gather"}
        assert {b: len(d) for b, d in log.records["all_gather"].items()} \
            == {b: 1 for b in sizes}
        assert {log.axes["all_gather", b] for b in sizes} == {"data"}
    finally:
        configure_comms_logger(was)
        log.reset()


def test_comm_ledger_keeps_the_guess_without_a_catalogue():
    """The lazy jit path books the guessed reduction; a step that loses its
    AOT program goes back to the guess and the dropped program's records
    are no step's traffic."""
    engine, batch = _engine(2)
    log = get_comms_logger()
    was = log.enabled
    log.reset()
    configure_comms_logger(True)
    guess = {"reduce_scatter": {
        "count": 1.0, "bytes": COUNT * 4.0, "wire_bytes": COUNT * 4.0,
        "time_s": 0.0}}
    try:
        engine.train_batch(batch)
        delta, _ = engine._comm_step_delta()
        assert delta == guess
        assert engine.warmup(batch)
        engine._forget_aot()       # the guess's booking goes with it ...
        assert engine.warmup(batch)
        engine.train_batch(batch)
        delta, _ = engine._comm_step_delta()
        assert "all_gather" in delta and delta != guess   # ... the catalogue's
        engine._forget_aot()
        assert engine._comm_booked is None
        for _ in range(2):
            engine.train_batch(batch)
            delta, _ = engine._comm_step_delta()
            assert delta == guess
    finally:
        configure_comms_logger(was)
        log.reset()
        engine.close()
        mesh_mod.reset_topology()


# ----------------------------------------------------------------------
# the catalogue's reader on texts whose every number is known
HLO = """HloModule jit_train_step, is_scheduled=true, entry_computation_layout={()->f32[]}, num_partitions=4

%add.1 (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.2 = f32[] add(%x, %y)
}

%all-reduce-scatter.7 (input.1: bf16[64,32]) -> bf16[16,32] {
  %input.1 = bf16[64,32]{1,0} parameter(0)
  %all-reduce.9 = bf16[64,32]{1,0:T(8,128)(2,1)} all-reduce(%input.1), channel_id=11, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%add.1
  %partition-id.1 = u32[] partition-id()
  ROOT %dynamic-slice.3 = bf16[16,32]{1,0} dynamic-slice(%all-reduce.9, %partition-id.1, %partition-id.1), dynamic_slice_sizes={16,32}
}

%fused_computation.5 (p: bf16[16,32]) -> (bf16[16,32], bf16[64,32], u32[]) {
  %p = bf16[16,32]{1,0} parameter(0)
  %all-gather.20 = bf16[64,32]{1,0} all-gather(%p), channel_id=12, replica_groups=[1,4]<=[4], dimensions={0}, use_global_device_ids=true
  ROOT %custom-call.1 = (bf16[16,32]{1,0}, bf16[64,32]{1,0}, u32[]) custom-call(%p, %all-gather.20), custom_call_target="AsyncCollectiveStart"
}

%fused_computation.6 (q: (bf16[16,32], bf16[64,32], u32[])) -> bf16[64,32] {
  %q = (bf16[16,32]{1,0}, bf16[64,32]{1,0}, u32[]) parameter(0)
  %all-gather.21 = bf16[64,32]{1,0} all-gather(%q), channel_id=12, replica_groups=[1,4]<=[4], dimensions={0}, use_global_device_ids=true
  ROOT %custom-call.2 = bf16[64,32]{1,0} custom-call(%all-gather.21), custom_call_target="AsyncCollectiveDone"
}

%cond.1 (c: (s32[], bf16[16,32])) -> pred[] {
  %c = (s32[], bf16[16,32]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%c), index=0
  %constant.8 = s32[] constant(6)
  ROOT %lt.1 = pred[] compare(%i, %constant.8), direction=LT
}

%body.1 (b: (s32[], bf16[16,32])) -> (s32[], bf16[16,32]) {
  %b = (s32[], bf16[16,32]{1,0}) parameter(0)
  %w = bf16[16,32]{1,0} get-tuple-element(%b), index=1
  %async-collective-start.2 = (bf16[16,32]{1,0}, bf16[64,32]{1,0}, u32[]) fusion(%w), kind=kCustom, calls=%fused_computation.5, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/ffn/dot_general"}
  %async-collective-done.2 = bf16[64,32]{1,0} fusion(%async-collective-start.2), kind=kCustom, calls=%fused_computation.6, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/ffn/dot_general"}
  %fusion.44 = bf16[16,32]{1,0} fusion(%async-collective-done.2), kind=kCustom, calls=%all-reduce-scatter.7, metadata={op_name="jit(train_step)/transpose(jvp())/while/body/closed_call/ffn/dot_general"}
  %ag-start = (f32[8]{0}, f32[32]{0}) all-gather-start(%w), channel_id=13, replica_groups={{0,1,2,3}}, dimensions={0}
  %ag-done = f32[32]{0} all-gather-done(%ag-start)
  %i2 = s32[] get-tuple-element(%b), index=0
  ROOT %t = (s32[], bf16[16,32]{1,0}) tuple(%i2, %fusion.44)
}

ENTRY %main.1 (a: bf16[16,32]) -> f32[] {
  %a = bf16[16,32]{1,0} parameter(0)
  %zero = s32[] constant(0)
  %tuple.1 = (s32[], bf16[16,32]{1,0}) tuple(%zero, %a)
  %while.1 = (s32[], bf16[16,32]{1,0}) while(%tuple.1), condition=%cond.1, body=%body.1
  %g = f32[100]{0} parameter(1)
  %reduce-scatter.3 = f32[25]{0} reduce-scatter(%g), channel_id=14, replica_groups={{0,1,2,3}}, dimensions={0}, to_apply=%add.1, metadata={op_name="jit(train_step)/transpose(jvp())/head/dot_general"}
  %all-to-all.4 = f32[25]{0} all-to-all(%reduce-scatter.3), channel_id=15, replica_groups={{0,1,2,3}}, dimensions={0}, metadata={op_name="jit(train_step)/optimizer/update/add"}
  %cp-start = (f32[25]{0}, f32[25]{0}, u32[], u32[]) collective-permute-start(%all-to-all.4), channel_id=16, source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
  %cp-done = f32[25]{0} collective-permute-done(%cp-start)
  ROOT %all-reduce.5 = f32[] all-reduce(%cp-done), channel_id=17, replica_groups={}, to_apply=%add.1, metadata={op_name="jit(train_step)/optimizer/norm/reduce_sum"}
}
"""

#: name -> (kind, bytes, asynchronous, in a loop, runs, bytes sent a run)
BY_HAND_HLO = {
    "async-collective-start.2": ("all-gather", 64 * 32 * 2, True, True, 6,
                                 64 * 32 * 2 * 3 // 4),
    "fusion.44": ("reduce-scatter", 64 * 32 * 2, False, True, 6,
                  64 * 32 * 2 * 3 // 4),
    "ag-start": ("all-gather", 32 * 4, True, True, 6, 32 * 4 * 3 // 4),
    "reduce-scatter.3": ("reduce-scatter", 100 * 4, False, False, 1, 300),
    "all-to-all.4": ("all-to-all", 100, False, False, 1, 75),
    "cp-start": ("collective-permute", 100, True, False, 1, 100),
    "all-reduce.5": ("all-reduce", 4, False, False, 1, 6),
}


@pytest.mark.parametrize("name", sorted(BY_HAND_HLO))
def test_catalogue_of_a_hand_written_module(name):
    """A fusion that holds a reduce-scatter (the TPU compiler's
    ``all-reduce-scatter``: an all-reduce and this chip's slice) is listed
    under the fusion's name with its kind and the operand's bytes; an
    asynchronous pair, by opcode or as the compiler's start / done fusions
    around one channel, is one entry; a loop's body counts by its trip
    count, read from the condition where the loop does not state it."""
    found = {c.name: c for c in coll.catalogue(HLO)}
    assert set(found) == set(BY_HAND_HLO)
    c = found[name]
    kind, size, asynchronous, in_loop, runs, sent = BY_HAND_HLO[name]
    assert (c.kind, c.bytes, c.asynchronous, c.in_loop, c.runs, c.members,
            c.runs_known, c.sent_bytes) == (kind, size, asynchronous,
                                            in_loop, runs, (0, 1, 2, 3),
                                            True, sent)


@pytest.mark.parametrize("text, first", [
    ("replica_groups={{0,2},{1,3}}", (0, 2)),
    ("replica_groups=[2,4]<=[8]", (0, 1, 2, 3)),
    ("replica_groups=[4,2]<=[2,4]T(1,0)", (0, 4)),
    ("replica_groups=[2,4]<=[2,2,2]T(1,0,2)", (0, 1, 4, 5)),
    ("source_target_pairs={{0,2},{2,0}}", (0, 2)),
    ("replica_groups={}", tuple(range(8))),
])
def test_first_replica_group_of_each_form(text, first):
    assert coll._members(f"(%x), channel_id=1, {text}, dimensions={{0}}",
                         8) == first


def test_a_group_is_replayed_along_its_own_axis_or_none():
    """Partition ids are places in the mesh: a group along ``model`` is not
    booked on ``data``, and one that spans two axes on none."""
    engine, _ = _engine(1, chips=2, model=2)
    try:
        shape = dict(engine.topo.mesh.shape)
        assert (shape["data"], shape["model"]) == (2, 2)
        ids = np.arange(4).reshape([shape[a] for a in
                                    engine.topo.mesh.axis_names])
        along = lambda axis: tuple(np.moveaxis(
            ids, engine.topo.mesh.axis_names.index(axis), -1
        ).reshape(-1, 2)[0].tolist())
        assert engine._axis_of(along("data")) == "data"
        assert engine._axis_of(along("model")) == "model"
        assert engine._axis_of((0, 1, 2, 3)) is None
    finally:
        engine.close()
        mesh_mod.reset_topology()


def test_catalogue_flags_a_loop_of_unknown_length_and_sums_by_kind():
    unknown = HLO.replace("direction=LT", "direction=NE")
    found = coll.catalogue(unknown)
    assert {c.runs for c in found} == {1}
    assert [c.name for c in found if not c.runs_known] == [
        "async-collective-start.2", "fusion.44", "ag-start"]
    by = coll.totals(coll.catalogue(HLO))
    assert by["all-gather"] == {
        "count": 12, "bytes": 6 * (4096 + 128),
        "sent_bytes": 6 * (3072 + 96)}
    assert by["reduce-scatter"]["sent_bytes"] == 6 * 3072 + 300
    plan = {"plan_bytes": 123_000_000, "gather_bytes": 100_000_000,
            "reduce_bytes": 23_000_000}
    line = coll.describe(coll.catalogue(HLO), plan)
    assert line.startswith("train step collectives: ") \
        and "reduce-scatter x7" in line \
        and "plan 0.123 GB (gathers 0.100, reductions 0.023)" in line
    assert "counted once" in coll.describe(found, plan)
    assert coll.catalogue("") == [] and "none" in coll.describe([], plan)
