"""The cell ``sdar-30b-a3b.answer`` at a size the CPU holds: the mix is the
issue's and never offers the mask id; the runner's reduction on hand-made
stamps; end to end through the command; the program against
``reference/sdar_moe.py`` through the cache, pass by pass, and the fp8,
causal-mask and stale-commit controls each told apart; each pass's decisions
held to the reference's plain rule, and four faults of the rule each
counted; and the reader this
PR brought (``block_passes``) on hand-made spans, beside
``named_scope_device`` on the scope ``ffn/experts``."""

import importlib.util
import json
import os
import types

import numpy as np
import pytest

from benchmarks import harness, program_trace as pt
from benchmarks.generators import open_loop_ruled
from benchmarks.readers import block_passes, named_scope_device
from benchmarks.runners import serve_block_diffusion as serve
from benchmarks.runners.serve_open_loop import Sent

_spec = importlib.util.spec_from_file_location(
    "bench_conftest", os.path.join(os.path.dirname(__file__), "conftest.py"))
bench_conftest = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench_conftest)   # make_tiny_root, not a fixture
_edit, make_tiny_root = bench_conftest._edit, bench_conftest.make_tiny_root

CELL = "sdar-30b-a3b.answer"
SEED = 4_500_000_045
MASK = 151669
TINY_MOE = {"moe_intermediate_size": 32, "num_experts": 8,
            "num_experts_per_tok": 2}
TINY_ANSWER = {"prompt_tokens": {"dist": "lognormal", "median": 24,
                                 "sigma": 0.5, "min": 8, "max": 48},
               "output_tokens": {"dist": "lognormal", "median": 8,
                                 "sigma": 0.5, "min": 4, "max": 12},
               "lead_seconds": 1, "grace_seconds": 30}
MIX = json.load(open(os.path.join(harness.HERE, "traffic", "answer.json")))


# ----------------------------------------------------------------------
# the mix
def test_the_mix_is_the_issues():
    assert MIX["generator"] == "open_loop_ruled" and MIX["order_seed"] == 45
    assert MIX["prompt_tokens"] == {"dist": "lognormal", "median": 128,
                                    "sigma": 0.8, "min": 16, "max": 512}
    assert MIX["output_tokens"] == {"dist": "lognormal", "median": 256,
                                    "sigma": 0.5, "min": 64, "max": 512}
    assert (MIX["lead_seconds"], MIX["grace_seconds"]) == (20, 30)
    assert MIX["arrivals"] == {"process": "stratified_exponential"}
    cell = harness.Cell(CELL)
    assert cell.spec["runner"] == "serve_block_diffusion"
    assert cell.config["assumed"]["mask_token_id"] == MASK
    assert cell.config["num_hidden_layers"] == 7
    assert cell.config["published"] == {"num_hidden_layers": 48}


def test_the_traffic_never_offers_the_mask_id():
    """The generator draws ids from 1 to the vocabulary less one, the mask
    id among them; the runner puts the id below in its place."""
    cell = harness.Cell(CELL)
    assert serve.without_mask_id([5, MASK, MASK - 1, 7], MASK) \
        == [5, MASK - 1, MASK - 1, 7]

    class Drawn:                      # a generator that draws it every time
        @staticmethod
        def generate(mix, rate, seconds, seed, vocab):
            out = open_loop_ruled.generate(mix, rate, seconds, seed, vocab)
            for a in out:
                a.prompt[::3] = [MASK] * len(a.prompt[::3])
            return out

    got = serve.arrivals_of(cell, Drawn, 2.0, 3.0, SEED)
    plain = open_loop_ruled.generate(cell.traffic, 2.0, 3.0, SEED,
                                     cell.config["vocab_size"])
    assert [len(a.prompt) for a in got] == [len(a.prompt) for a in plain]
    assert all(1 <= t < cell.config["vocab_size"] and t != MASK
               for a in got for t in a.prompt)
    assert all(a.prompt[0] == MASK - 1 for a in got)


# ----------------------------------------------------------------------
# the reduction, on hand-made stamps
def _sent(due, n_prompt, n_out, times):
    s = Sent(due, n_prompt, n_out)
    s.sent, s.times = due, list(times)
    return s


def test_emissions_group_tokens_by_block():
    # a prompt of 6: the first block holds 2 generated tokens, then 4, 4
    s = _sent(0.0, 6, 10, [1.0, 1.0, 1.5, 1.5, 1.5, 1.5, 2.5, 2.5, 2.5, 2.5])
    assert serve.emissions(s, 4) == [[1.0, 2], [1.5, 4], [2.5, 4]]
    assert serve.token_waits(s, 4) == [0.125] * 4 + [0.25] * 4
    # stamps of one emission that differ: timed at its last
    s = _sent(0.0, 8, 6, [1.0, 1.1, 1.2, 1.3, 2.0, 2.3])
    assert serve.emissions(s, 4) == [[1.3, 4], [2.3, 2]]
    assert serve.token_waits(s, 4) == pytest.approx([0.5, 0.5])


def test_reduction_on_hand_made_stamps():
    """A first block of ``4 - r`` tokens, a failed request, a single-block
    answer that contributes no gap."""
    sent = [
        _sent(0.0, 6, 10, [1.0] * 2 + [1.4] * 4 + [2.2] * 4),    # 0.1, 0.2
        _sent(0.5, 8, 3, [2.0] * 3),                # one block: no gap
        _sent(1.0, 4, 8, [3.0] * 4),                # unfinished: failed
        _sent(-1.0, 4, 4, [0.5] * 4),               # the lead-in's
    ]
    out = serve.reduce_window(sent, 10.0, 5.0, 4)
    assert (out["attempted"], out["failed"]) == (3, 1)
    # waits: 4 x 0.1, 4 x 0.2, and the failed request's 15 - 3 = 12 s
    assert out["itl_p50_ms"] == pytest.approx(200.0)
    assert out["itl_p95_ms"] == pytest.approx(
        harness.percentile([0.1] * 4 + [0.2] * 4 + [12.0], 95) * 1e3)
    assert out["ttft_p50_ms"] == pytest.approx(1500.0)    # 1.0, 1.5, 2.0
    # every token stamped inside the window, and a prompt at its first
    assert out["serve_tok_s"] == pytest.approx(
        (10 + 6 + 3 + 8 + 4 + 4 + 4 + 4) / 10.0)
    assert out["late_s"] == [0.0, 0.0, 0.0]


# ----------------------------------------------------------------------
# the cell, cut to the CPU's size
@pytest.fixture(scope="module")
def sdar_root(tmp_path_factory):
    """``make_tiny_root`` knows nothing of the experts' keys, of ``answer``
    or of a mask id inside a vocabulary of 256: cut those here."""
    root = make_tiny_root(str(tmp_path_factory.mktemp("sdar")))
    b = os.path.join(root, "benchmarks")

    def cut(c):
        c.update(TINY_MOE)
        c["assumed"]["mask_token_id"] = 255
    _edit(os.path.join(b, "configs", "sdar-30b-a3b.json"), cut)
    _edit(os.path.join(b, "traffic", "answer.json"),
          lambda t: t.update(TINY_ANSWER))
    # bfloat16 on the CPU at width 64 is coarser than at 2048 on the chip
    _edit(os.path.join(b, "workloads", CELL + ".json"),
          lambda w: w["check"].update(reference_tokens=64, limits={
              "logit_err_median": 0.05, "logit_err_p75": 0.1,
              "logit_err_max": 0.5, "decided_mismatch": 0}))
    return root


def test_adapter_builds_from_a_file_cut_by_make_tiny_root(tiny_root):
    cell = harness.Cell(CELL, root=tiny_root)
    model = harness.find("architectures", "sdar_moe").build(
        cell.config, cell.n_layers)
    c = model.config
    assert (c.d_model, c.n_heads, c.n_kv_heads, c.head_dim) == (64, 4, 2, 16)
    assert (c.n_experts, c.top_k, c.d_ff) == (128, 8, 768)
    assert c.qk_norm and c.qk_norm_heads and not c.tie_embeddings
    assert (c.attn_block, c.mask_token_id, c.denoise_tokens) == (4, MASK, 2)


def test_cell_runs_end_to_end_at_a_tiny_size(sdar_root, run_cell):
    rc, last, out = run_cell(sdar_root, "--workload", CELL, "--seed",
                             str(SEED), "--seconds", "2", "--trace", "0")
    assert rc == 0, out[-3000:]
    assert set(last["metrics"]) == {"itl_p50_ms", "setup_s"}
    assert all(m["value"] > 0 for m in last["metrics"].values())
    assert last["attempted"] > 0 and last["failed"] == 0
    checks = [l for l in out.splitlines() if l.startswith("check ")]
    precision = [l for l in checks if "_err" in l or "decided" in l]
    assert len(precision) == 4 and all(l.endswith(" ok") for l in precision), \
        checks
    assert any("kernel_missing" in l and "OUTSIDE" in l for l in checks)
    assert last["correct"] is False          # no Pallas kernel on the CPU
    assert "compiled_in_window: 0 " in out and "undrained: 0 " in out


@pytest.fixture(scope="module")
def served(sdar_root):
    cell = harness.Cell(CELL, root=sdar_root)
    model, _, engine = serve.build_engine(cell, SEED)
    params = serve.contextual_mask_row(engine, serve.mask_id(cell))
    prompts, passes = serve.check_passes(cell, engine, SEED)
    want = serve.reference_logits(cell, params, passes)
    return cell, model, params, engine, prompts, passes, want


def test_reference_agrees_pass_by_pass_through_the_cache(served):
    cell, model, params, engine, prompts, passes, want = served
    assert engine.block_length == 4 and "lm_head" in params
    # the mask id's row is zero, in the engine's tree and the reference's
    embed = np.asarray(engine.params["tok_embed"], np.float32)
    assert engine.params is params and not embed[255].any()
    assert np.abs(embed[254]).max() > 0.1
    # so a sequence's masked positions are filled from its context: the
    # sequences do not all decide one token
    decided = {int(np.argmax(q["logits"][j])) for q in passes
               for j in range(4) if q["state"][len(q["state"]) - 4 + j] == 255}
    assert len(decided) >= len(prompts)
    assert params["layers"]["q_norm_w"].shape == (2, 16)
    gains = np.asarray(params["layers"]["q_norm_w"], np.float32)
    assert np.abs(gains - 1).max() > 0.01     # the chip tests the gains
    # every sequence: the block its prompt's tail opens (where it has one)
    # and two whole blocks, two passes a whole block
    for i, p in enumerate(prompts):
        mine = [q for q in passes if q["seq"] == i]
        tail = len(p) % 4
        assert len(mine) == 4 + (0 if not tail else 1 if tail >= 2 else 2)
        assert mine[0]["before"] is None and mine[-1]["before"] is not None
        assert mine[-1]["state"][:len(p)] == p
    got = np.stack([q["logits"] for q in passes])
    err = serve.position_errors(got, want)
    assert err.size == 4 * len(passes) >= len(prompts) * 16
    # bfloat16 at width 64 on the CPU (float32:
    # tests/test_block_diffusion_engine.py)
    # (a masked position's state is its layers' output alone, the mask id's
    # row being zero: a router tie that falls the other way moves one)
    assert np.median(err) < 0.05 and err.max() < 0.5, err


@pytest.mark.parametrize("control", serve.CONTROLS)
def test_control_differs_from_the_reference(served, control):
    cell, model, params, engine, prompts, passes, want = served
    got = np.stack([q["logits"] for q in passes])
    sound = float(np.median(serve.position_errors(got, want)))
    low = serve.reference_logits(cell, params, passes, control)
    err = serve.position_errors(low, want)
    if control == "stale":     # it shows to the passes that read a block
        read = np.repeat([q["before"] is not None for q in passes], 4)
        assert err[~read].max() < 1e-5
        err = err[read]
    assert np.median(err) > 1.5 * sound, (control, np.median(err), sound)


# ----------------------------------------------------------------------
# what a pass decides
def test_the_plain_rule_by_hand():
    ref = harness.find("reference", "sdar_moe")
    logits = np.zeros((4, 6))
    logits[0, 1] = 3.0                 # confident
    logits[1, 5] = 9.0                 # the mask id itself: never decided
    logits[1, 2] = 1.0
    logits[2, 3] = 2.0                 # lanes 2 and 3 tie: the lower first
    logits[3, 4] = 2.0
    masked = np.array([True, True, True, True])
    assert ref.decide(logits, masked, 2, 5)[0] == [(0, 1), (2, 3)]
    took, gap = ref.decide(logits, masked, 3, 5)
    assert took == [(0, 1), (2, 3), (3, 4)] and 0 < gap < 1
    # a decided position is never chosen again; fewer masked than n
    only = np.array([False, True, False, False])
    assert ref.decide(logits, only, 2, 5) == ([(1, 2)], np.inf)
    # a tie between the last taken and the first left reads a gap of zero
    assert ref.decide(logits, np.array([False, False, True, True]), 1, 5) \
        == ([(2, 3)], 0.0)


def test_every_pass_decides_what_the_plain_rule_decides(served):
    cell, model, params, engine, prompts, passes, want = served
    got = serve.decisions_unlike(cell, passes)
    assert got["unlike"] == 0 and got["compared"] >= len(passes) - 2
    # two a pass, and a prompt's tail of three leaves one
    assert got["tokens"] == sum(min(2, q["state"][-4:].count(255))
                                for q in passes)


def _faulty(passes, fault):
    """The passes as an engine with a fault in its rule would leave them."""
    ref = harness.find("reference", "sdar_moe")
    out = []
    for i, q in enumerate(passes):
        q = dict(q)
        masked = np.asarray(q["state"][-4:]) == 255
        if fault == "lowest_confidence":     # shows where over two are masked
            every = ref.decide(q["logits"], masked, 4, 255)[0]
            if len(every) > 2:
                q["decided"] = [d for d in every if d not in q["decided"]]
        elif fault == "one_a_pass":
            q["decided"] = q["decided"][:1]
        elif fault == "shifted_by_one":      # the next position's token
            q["decided"] = [(n, int(np.argmax(q["logits"][(n + 1) % 4])))
                            for n, _ in q["decided"]]
        elif fault == "another_slots_rows":
            q["logits"] = passes[(i + 1) % len(passes)]["logits"]
        out.append(q)
    return out


@pytest.mark.parametrize("fault", ["lowest_confidence", "one_a_pass",
                                   "shifted_by_one", "another_slots_rows"])
def test_a_fault_in_the_rule_is_counted(served, fault):
    cell, model, params, engine, prompts, passes, want = served
    got = serve.decisions_unlike(cell, _faulty(passes, fault))
    whole = sum(q["state"][-4:].count(255) == 4 for q in passes)
    assert got["unlike"] >= whole - 2 > 0, (got, whole)


def test_router_margins_of_the_reference(served):
    cell, model, params, engine, prompts, passes, want = served
    ref = harness.find("reference", "sdar_moe")
    q = passes[-1]
    n = len(q["state"])
    tokens = np.zeros((2, 64), np.int32)
    tokens[0, :n] = tokens[1, :n] = q["state"]
    tokens[1, n:] = 7                        # later blocks are invisible
    rows, cols = np.repeat([0, 1], 4), np.tile(np.arange(n - 4, n), 2)
    m = ref.router_margins(params, tokens, rows, cols, cell.config,
                           cell.n_layers)
    assert m.shape == (8, cell.n_layers) and (m >= 0).all() and m.max() > 0
    np.testing.assert_allclose(m[:4], m[4:], rtol=1e-5, atol=1e-6)
    # and the forward is the one logits_at runs
    again = ref.logits_at(params, tokens, rows[:4], cols[:4], cell.config,
                          cell.n_layers)
    np.testing.assert_allclose(np.asarray(again), want[-1], rtol=1e-5,
                               atol=1e-5)


def test_error_numbers():
    got = serve.error_numbers(np.arange(101) / 100.0)
    assert got == {"logit_err_median": 0.5, "logit_err_p75": 0.75,
                   "logit_err_p90": 0.9, "logit_err_max": 1.0}


# ----------------------------------------------------------------------
# the readers
def test_traced_blocks_records_every_scheduled_lane(served):
    """``TracedEngine`` counts where ``seen`` moved; a block engine's moves
    over final K/V only. ``TracedBlocks`` against the schedule itself."""
    cell, model, params, engine, prompts, passes, want = served
    traced = serve.TracedBlocks(engine)
    packed, pack = [], engine._pack_splitfuse

    def spy():
        sched = pack()
        packed.append(sorted((take, seq.seen + take) for seq, take in sched))
        return sched

    engine._pack_splitfuse = spy
    uids = [77, 78, 79]
    prompts = [prompts[0], prompts[1], prompts[0][:9]]   # 9: a tail of one
    try:
        for u, p in zip(uids, prompts):
            engine.limit_stream(u, len(p) + 9)   # ends inside a block
        traced.put(uids[:2], [list(p) for p in prompts[:2]])
        traced.put(uids, [[], [], list(prompts[2])])
        while any(engine.seqs[u].pending for u in uids):
            live = [u for u in uids if engine.seqs[u].pending]
            traced.put(live, [[] for _ in live])
    finally:
        del engine._pack_splitfuse
        engine.flush(uids)
    assert [sorted(c["seqs"]) for c in traced.calls] == packed
    lanes = [n for c in traced.calls for n, _ in c["seqs"]]
    assert 8 in lanes and 4 in lanes and len(traced.calls) > 6
    # a generated block's lanes run twice at the least (TracedEngine's rule
    # would count them once, where they became final)
    assert sum(lanes) > sum(len(p) + 9 for p in prompts) + 2 * 3 * 4


def test_block_passes_on_hand_made_spans():
    spans = [{"block_seqs": 3, "decided": 0, "commits": 0, "prefill": 12},
             {"block_seqs": 3, "decided": 6, "commits": 0, "prefill": 0},
             {"block_seqs": 2, "decided": 6, "commits": 1, "prefill": 0},
             {"lanes": 64}]                       # no block attributes
    assert block_passes.passes_per_token(spans) == pytest.approx(8 / 12)
    assert block_passes.passes_per_token([{"lanes": 64}]) is None
    assert block_passes.passes_per_token(
        [{"block_seqs": 2, "decided": 0}]) is None


def _program(put_attrs, ops):
    spans = [pt.Span("ragged.put", float(i), float(i) + 0.9, dict(a), None)
             for i, a in enumerate(put_attrs)]
    return pt.ProgramTrace(spans, {0: ops})


def test_readers_read_the_program_record():
    attrs = [{"prefill": 0, "block_seqs": 4, "decided": 8, "commits": 2},
             {"prefill": 16, "block_seqs": 4, "decided": 8, "commits": 0}]
    op = lambda name, path, a, b: types.SimpleNamespace(
        name=name, op_name=path, start=a, end=b)
    ops = [op("fusion.1", "jit(step)/ffn/experts/ragged_dot", 0.1, 0.3),
           op("fusion.2", "jit(step)/ffn/router/dot", 0.3, 0.4),
           op("fusion.3", "jit(step)/ffn/experts/ragged_dot", 1.1, 1.6)]
    program = _program(attrs, ops)
    record = {"window": (0.0, 2.0), "program_trace": program}
    assert block_passes.read(record, {"span": "ragged.put"}) \
        == pytest.approx(0.5)
    # the tick without a prompt chunk alone: 0.2 s under ffn/experts
    spec = harness.Cell(CELL).metric_spec("experts_device_ms.serve")
    assert named_scope_device.read(record, spec["args"]) \
        == pytest.approx(200.0)
    # a program without the attributes (the parent): nothing to read
    record["program_trace"] = _program([{"prefill": 0}], ops)
    assert block_passes.read(record, {"span": "ragged.put"}) is None
