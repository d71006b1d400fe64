"""The cell ``granite-4.0-h-micro.assist`` at a size the CPU holds: the
generator ``open_loop_ruled`` (one schedule for every seed, the sets of
``open_loop``); end to end through the command; the program against
``reference/granite_hybrid.py`` through both pools, and the fp8 control told
apart; the Mamba-2 step's operation and byte counts against hand counts; and
the reader PR 43 brought (``mamba2_roofline``) and ``named_scope_device`` on
the scope ``ssm``, on a hand-built trace and on a program without the scope
or without the span's ``state_layers``."""

import collections
import importlib.util
import json
import os

import numpy as np
import pytest

from benchmarks import harness, program_trace as pt
from benchmarks.generators import open_loop, open_loop_ruled
from benchmarks.ops_bytes import mamba2
from benchmarks.readers import mamba2_roofline, named_scope_device
from benchmarks.runners import serve_open_loop as serve

_spec = importlib.util.spec_from_file_location(
    "bench_conftest", os.path.join(os.path.dirname(__file__), "conftest.py"))
bench_conftest = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench_conftest)   # make_tiny_root, not a fixture
_edit, make_tiny_root = bench_conftest._edit, bench_conftest.make_tiny_root

CELL = "granite-4.0-h-micro.assist"
SEED = 4_300_000_043
TINY_MAMBA = {"num_hidden_layers": 6, "shared_intermediate_size": 128,
              "layer_types": ["mamba", "mamba", "attention"] * 2,
              "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 8,
              "mamba_chunk_size": 16}
TINY_ASSIST = {"prompt_tokens": {"dist": "lognormal", "median": 24,
                                 "sigma": 0.5, "min": 8, "max": 48},
               "output_tokens": {"dist": "lognormal", "median": 8,
                                 "sigma": 0.5, "min": 4, "max": 12},
               "lead_seconds": 1, "grace_seconds": 30}
MIX = json.load(open(os.path.join(harness.HERE, "traffic", "assist.json")))


# ----------------------------------------------------------------------
# the generator
def test_the_mix_is_the_issues():
    assert MIX["generator"] == "open_loop_ruled" and MIX["order_seed"] == 43
    assert MIX["prompt_tokens"] == {"dist": "lognormal", "median": 160,
                                    "sigma": 0.8, "min": 16, "max": 1024}
    assert MIX["output_tokens"] == {"dist": "lognormal", "median": 256,
                                    "sigma": 0.6, "min": 64, "max": 768}
    assert (MIX["lead_seconds"], MIX["grace_seconds"]) == (20, 30)
    assert MIX["arrivals"] == {"process": "stratified_exponential"}


def test_two_seeds_are_offered_one_schedule_and_other_ids():
    a = open_loop_ruled.generate(MIX, 5.4, 30.0, 1, 100352)
    b = open_loop_ruled.generate(MIX, 5.4, 30.0, 4_300_000_043, 100352)
    assert [x.due for x in a] == [x.due for x in b]
    assert [len(x.prompt) for x in a] == [len(x.prompt) for x in b]
    assert [x.max_new_tokens for x in a] == [x.max_new_tokens for x in b]
    assert [x.prompt for x in a] != [x.prompt for x in b]
    assert a[0].prompt != b[0].prompt
    lead = [x for x in a if x.due < 0]
    assert len(lead) == round(5.4 * 20) and len(a) - len(lead) == 162
    assert lead[0].due == -20.0 and a[len(lead)].due == 0.0
    again = open_loop_ruled.generate(MIX, 5.4, 30.0, 1, 100352)
    assert [x.prompt for x in again] == [x.prompt for x in a]
    other = open_loop_ruled.generate(dict(MIX, order_seed=44), 5.4, 30.0, 1,
                                     100352)
    assert [x.due for x in other] != [x.due for x in a]


@pytest.mark.parametrize("rate", [2.0, 5.4])
def test_its_sets_are_open_loops(rate):
    """The same lengths and the same gaps, lead-in and window apart: only
    the order differs, and the order is the mix's, not the seed's."""
    ruled = open_loop_ruled.generate(MIX, rate, 30.0, 7, 100352)
    plain = open_loop.generate(MIX, rate, 30.0, 7, 100352)
    assert len(ruled) == len(plain)
    assert open_loop_ruled.quantile is open_loop.quantile
    for part in (lambda x: x.due < 0, lambda x: x.due >= 0):
        r, p = [x for x in ruled if part(x)], [x for x in plain if part(x)]
        assert sorted(len(x.prompt) for x in r) \
            == sorted(len(x.prompt) for x in p)
        assert sorted(x.max_new_tokens for x in r) \
            == sorted(x.max_new_tokens for x in p)
        # n gaps make n arrivals: the first arrival sits at the part's
        # start and the gap the order puts first is not walked, so each
        # side shows all but one of the same n quantile gaps
        n = len(r)
        span = 20.0 if r[0].due < 0 else 30.0
        q = -np.log1p(-(np.arange(n) + 0.5) / n)
        full = collections.Counter(np.round(q * span / q.sum(), 9))
        for xs in (r, p):
            seen = collections.Counter(np.round(np.diff(
                [x.due for x in xs]), 9))
            assert not seen - full and sum((full - seen).values()) == 1


# ----------------------------------------------------------------------
# the cell, cut to the CPU's size
@pytest.fixture(scope="module")
def granite_root(tmp_path_factory):
    """``make_tiny_root`` knows nothing of ``mamba_*``, ``layer_types``,
    ``shared_intermediate_size`` or ``assist``: cut those here."""
    root = make_tiny_root(str(tmp_path_factory.mktemp("granite")))
    b = os.path.join(root, "benchmarks")
    _edit(os.path.join(b, "configs", "granite-4.0-h-micro.json"),
          lambda c: c.update(TINY_MAMBA))
    _edit(os.path.join(b, "traffic", "assist.json"),
          lambda t: t.update(TINY_ASSIST))
    # bfloat16 on the CPU at width 64 is coarser than at 2048 on the chip
    _edit(os.path.join(b, "workloads", CELL + ".json"),
          lambda w: w["check"].update(limits={"logit_err_median": 0.05,
                                              "logit_err_max": 0.5}))
    return root


def test_adapter_builds_from_a_file_cut_by_make_tiny_root(tiny_root):
    """Two layers, both Mamba-2, the published ``mamba_*`` sizes and the
    four scalars kept."""
    cell = harness.Cell(CELL, root=tiny_root)
    model = harness.find("architectures", "granite_hybrid").build(
        cell.config, cell.n_layers)
    c = model.config
    assert c.layer_types == ("mamba", "mamba") and c.d_model == 64
    assert (c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state) == (64, 64, 128)
    assert (c.attn_scale, c.embedding_multiplier, c.residual_multiplier,
            c.logits_scaling) == (1 / 64, 12.0, 0.22, 8.0)
    assert c.tie_embeddings and c.position == "none"


def test_cell_runs_end_to_end_at_a_tiny_size(granite_root, run_cell):
    rc, last, out = run_cell(granite_root, "--workload", CELL, "--seed",
                             str(SEED), "--seconds", "2", "--trace", "0")
    assert rc == 0, out[-3000:]
    assert set(last["metrics"]) == {"itl_p50_ms", "setup_s"}
    assert all(m["value"] > 0 for m in last["metrics"].values())
    assert last["attempted"] > 0 and last["failed"] == 0
    checks = [l for l in out.splitlines() if l.startswith("check ")]
    precision = [l for l in checks if "_err" in l]
    assert precision and all(l.endswith(" ok") for l in precision), checks
    assert any("kernel_missing" in l and "OUTSIDE" in l for l in checks)
    assert last["correct"] is False          # no Pallas kernel on the CPU
    assert "compiled_in_window: 0 " in out and "undrained: 0 " in out


@pytest.fixture(scope="module")
def served(granite_root):
    cell = harness.Cell(CELL, root=granite_root)
    model, params, engine = serve.build_engine(cell, SEED)
    prompts = serve.check_prompts(cell, SEED)
    steps = cell.spec["check"]["decode_steps"]
    fed, got = serve.engine_logits(engine, prompts, steps)
    want = serve.reference_logits(cell, params, fed,
                                  [len(p) for p in prompts], steps)
    return cell, model, params, engine, prompts, fed, got, want


def test_reference_agrees_through_both_pools(served):
    cell, model, params, engine, prompts, fed, got, want = served
    assert model.config.layer_types == ("mamba", "mamba", "full") * 2
    # two periods of three: a leaf a layer of a period, two runs in it
    assert engine._periods == 2
    assert len(engine.kv_pool.k) == 1 and len(engine.kv_pool.state) == 2
    assert "lm_head" not in params                # the head is the embedding
    err = serve.position_errors(got, want)
    steps = cell.spec["check"]["decode_steps"]
    assert err.size == len(prompts) * (steps + 1)
    # bfloat16 at width 64 on the CPU (float32: tests/test_granite_hybrid.py)
    assert np.median(err) < 0.05 and err.max() < 0.1, err


def test_fp8_control_differs_from_the_reference(served):
    cell, model, params, engine, prompts, fed, got, want = served
    lens = [len(p) for p in prompts]
    steps = cell.spec["check"]["decode_steps"]
    sound = float(np.median(serve.position_errors(got, want)))
    control = serve.reference_logits(cell, params, fed, lens, steps, "fp8")
    assert np.median(serve.position_errors(control, want)) > 1.5 * sound


# ----------------------------------------------------------------------
# operations and bytes
@pytest.mark.parametrize("n,heads,hd,groups,state", [(1, 1, 2, 1, 3),
                                                     (50, 64, 64, 1, 128)])
def test_ssd_step_counts_against_hand_counts(n, heads, hd, groups, state):
    flops, moved = mamba2.ops_and_bytes(n, heads, hd, groups, state)
    s = heads * hd * state
    assert flops == n * (5 * s + 3 * heads * hd)
    # the state in and out; x and y, B and C, dt and the decay: float32
    assert moved == n * 4 * (2 * s + 2 * heads * hd + 2 * groups * state
                             + 2 * heads)
    if n == 1:
        assert (flops, moved) == (5 * 6 + 3 * 2, 4 * (12 + 4 + 6 + 2))
    else:   # ISSUE 43: 4,194,304 B of state a sequence, read and written
        assert moved / n == 4_194_304 + 4 * (8192 + 256 + 128)
        assert 6 * s > flops / n > 5 * s


# ----------------------------------------------------------------------
# the readers
J = "jit(step)/"


def _hand_record(state_layers=True):
    """Two decode-only ticks (5 and 3 sequences decode) and one with a
    prefill chunk; under ``ssm`` a projection, ``conv``, the step (a
    fusion, and a second one under a transform's wrapper) and a ``while``
    around the chunk loop's body, which counts once."""
    more = {"state_layers": 3} if state_layers else {}
    spans = [pt.Span("ragged.put", 0.010, 0.020,
                     dict(prefill=0, decode=5, **more), None),
             pt.Span("ragged.put", 0.030, 0.040,
                     dict(prefill=0, decode=3, **more), None),
             pt.Span("ragged.put", 0.050, 0.090,
                     dict(prefill=70, decode=2, **more), None)]
    L = J + "ssm/"
    ops = [pt.Op("fusion.1", 0.011, 0.012, L + "dot_general:"),
           pt.Op("fusion.2", 0.012, 0.0125, L + "conv/mul:"),
           pt.Op("fusion.3", 0.013, 0.015, L + "ssd_step/mul:"),
           pt.Op("fusion.4", 0.015, 0.016, J + "jvp(ssm)/ssd_step/add:"),
           pt.Op("while.5", 0.016, 0.018, L + "ssd_chunk/while:"),
           pt.Op("fusion.6", 0.016, 0.017, L + "ssd_chunk/while/body/dot_general:"),
           pt.Op("fusion.7", 0.018, 0.019, J + "attn/dot_general:"),
           pt.Op("fusion.3", 0.031, 0.032, L + "ssd_step/mul:"),
           pt.Op("fusion.3", 0.051, 0.060, L + "ssd_step/mul:")]
    cfg = {"layer_types": ["mamba"] * 3 + ["attention"], "mamba_n_heads": 2,
           "mamba_d_head": 4, "mamba_n_groups": 1, "mamba_d_state": 8}
    cell = type("Cell", (), {"config": cfg})()
    return {"program_trace": pt.ProgramTrace(spans, {0: ops}),
            "window": (0.0, 0.1), "cell": cell, "n_layers": 4,
            "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}}


def _args(metric):
    return dict(json.load(open(os.path.join(
        harness.HERE, "metrics", metric + ".json")))["args"])


def test_ssm_scope_reader_on_a_hand_built_trace():
    # tick 1: 1 + 0.5 + 2 + 1 + 1 (the while itself left out) = 5.5 ms;
    # tick 2: 1 ms; the prefill tick is not read: the median is 3.25
    assert named_scope_device.read(
        _hand_record(), _args("ssm_device_ms.serve")) == pytest.approx(3.25)


def test_roofline_reader_on_a_hand_built_trace():
    # 8 decoded sequences x the span's 3 state layers; by bytes at 1 GB/s;
    # 4 ms under ssm/ssd_step in the two decode-only ticks
    _, moved = mamba2.ops_and_bytes(8, 2, 4, 1, 8)
    want = 100.0 * (3 * moved / 1e9) / 0.004
    assert mamba2_roofline.read(
        _hand_record(), _args("ssd_step_roofline_pct")) == pytest.approx(want)


@pytest.mark.parametrize("what", ["no_scope", "no_state_layers"])
def test_roofline_reader_finds_nothing_in_the_parents_program(what):
    """The parent of PR 43: spans without ``state_layers``, no operation
    under ``ssm``; neither raises."""
    record = _hand_record(state_layers=what != "no_state_layers")
    if what == "no_scope":
        ops = [o for o in record["program_trace"].ops[0]
               if "ssm" not in o.op_name]
        record["program_trace"] = pt.ProgramTrace(
            record["program_trace"].spans, {0: ops})
        assert named_scope_device.read(
            record, _args("ssm_device_ms.serve")) is None
    assert mamba2_roofline.read(
        record, _args("ssd_step_roofline_pct")) is None
