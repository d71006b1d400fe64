"""The cell ``olmo-hybrid-7b.reason`` at a size the CPU holds: end to end
through the command; the program against ``reference/olmo_hybrid.py`` with a
prompt split over ticks, and the int8 control and an int8 program told
apart; the delta step's operation and byte counts against hand counts; and
the two readers PR 28 brought (``named_scope_device``,
``gated_delta_roofline``) on a hand-built trace, and on a program without
the scopes."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness, program_trace as pt
from benchmarks.ops_bytes import gated_delta
from benchmarks.readers import gated_delta_roofline, named_scope_device
from benchmarks.reference import mistral as ref_mistral
from benchmarks.runners import serve_open_loop as serve

_spec = importlib.util.spec_from_file_location(
    "bench_conftest", os.path.join(os.path.dirname(__file__), "conftest.py"))
bench_conftest = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench_conftest)   # make_tiny_root, not a fixture
_edit, make_tiny_root = bench_conftest._edit, bench_conftest.make_tiny_root

CELL = "olmo-hybrid-7b.reason"
SEED = 3_000_000_019
TINY_LINEAR = {"num_hidden_layers": 4, "linear_num_key_heads": 4,
               "linear_num_value_heads": 4, "linear_key_head_dim": 8,
               "linear_value_head_dim": 16}
TINY_REASON = {"prompt_tokens": {"dist": "lognormal", "median": 24,
                                 "sigma": 0.5, "min": 8, "max": 48},
               "output_tokens": {"dist": "lognormal", "median": 8,
                                 "sigma": 0.5, "min": 4, "max": 12},
               "lead_seconds": 1, "grace_seconds": 30}


@pytest.fixture(scope="module")
def hybrid_root(tmp_path_factory):
    """``make_tiny_root`` knows nothing of ``linear_*``, ``layer_types`` or
    ``reason``: cut those here (a whole period of layers, small heads)."""
    root = make_tiny_root(str(tmp_path_factory.mktemp("hybrid")))
    b = os.path.join(root, "benchmarks")
    _edit(os.path.join(b, "configs", "olmo-hybrid-7b.json"),
          lambda c: c.update(TINY_LINEAR))
    _edit(os.path.join(b, "traffic", "reason.json"),
          lambda t: t.update(TINY_REASON))
    # bfloat16 on the CPU at width 64 is coarser than at 3840 on the chip
    _edit(os.path.join(b, "workloads", CELL + ".json"),
          lambda w: w["check"].update(limits={"logit_err_median": 0.03,
                                              "logit_err_max": 0.5}))
    return root


def test_adapter_builds_from_a_file_cut_by_make_tiny_root(tiny_root):
    """Two layers, both linear, the published ``linear_*`` sizes kept."""
    cell = harness.Cell(CELL, root=tiny_root)
    model = harness.find("architectures", "olmo_hybrid").build(
        cell.config, cell.n_layers)
    c = model.config
    assert c.layer_types == ("linear", "linear") and c.d_model == 64
    assert (c.linear_n_k_heads, c.linear_k_dim, c.linear_v_dim) == (30, 96, 192)
    assert c.branch_norm and c.qk_norm and c.position == "none"


def test_cell_runs_end_to_end_at_a_tiny_size(hybrid_root, run_cell):
    rc, last, out = run_cell(hybrid_root, "--workload", CELL, "--seed",
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
def served(hybrid_root):
    cell = harness.Cell(CELL, root=hybrid_root)
    model, params, engine = serve.build_engine(cell, SEED)
    prompts = serve.check_prompts(cell, SEED)
    steps = cell.spec["check"]["decode_steps"]
    fed, got = serve.engine_logits(engine, prompts, steps)
    want = serve.reference_logits(cell, params, fed,
                                  [len(p) for p in prompts], steps)
    return cell, model, params, engine, prompts, fed, got, want


def test_reference_agrees_through_both_caches(served):
    cell, model, _, engine, prompts, fed, got, want = served
    assert model.config.layer_types == ("linear",) * 3 + ("full",)
    assert len(engine.kv_pool[0]) == 1 and len(engine.kv_pool[-1]) == 3
    err = serve.position_errors(got, want)
    steps = cell.spec["check"]["decode_steps"]
    assert err.size == len(prompts) * (steps + 1)
    # bfloat16 at width 64 on the CPU (float32: tests/test_olmo_hybrid.py)
    assert np.median(err) < 0.03 and err.max() < 0.08, err


def test_int8_control_and_int8_program_are_told_apart(served):
    cell, model, params, engine, prompts, fed, got, want = served
    lens = [len(p) for p in prompts]
    steps = cell.spec["check"]["decode_steps"]
    sound = float(np.median(serve.position_errors(got, want)))
    control = serve.reference_logits(cell, params, fed, lens, steps, "int8")
    assert np.median(serve.position_errors(control, want)) > 1.5 * sound
    from deepspeed_tpu.inference.ragged import RaggedInferenceEngine

    low_params = jax.tree_util.tree_map(
        lambda a: ref_mistral._round(a.astype(jnp.float32), -2, "int8")
        .astype(a.dtype) if a.ndim >= 3 else a, params)
    low = RaggedInferenceEngine(model, engine.config, params=low_params)
    fed_low, got_low = serve.engine_logits(low, prompts, steps)
    want_low = serve.reference_logits(cell, params, fed_low, lens, steps)
    assert np.median(serve.position_errors(got_low, want_low)) > 1.5 * sound


@pytest.mark.parametrize("n,heads,dk,dv", [(1, 1, 2, 3), (50, 30, 96, 192)])
def test_delta_step_counts_against_hand_counts(n, heads, dk, dv):
    flops, moved = gated_delta.ops_and_bytes(n, heads, dk, dv)
    state = heads * dk * dv
    assert flops == 7 * n * state
    # the state in and out, q and k, v and o, two gates: float32
    assert moved == n * 4 * (2 * state + heads * (2 * dk + 2 * dv + 2))
    if n == 50:   # ISSUE 28: 2.2 MB of state a sequence, read and written
        assert 2.2e6 < moved / n / 2 < 2.3e6


J = "jit(step)/"


def _hand_record():
    """Two decode-only ticks (5 and 3 sequences decode) and one with a
    prefill chunk; under ``linear_attn`` a projection, ``conv``, the step
    (a fusion, and a second one under a transform's wrapper) and a
    ``while`` around the chunk loop's body, which counts once."""
    spans = [pt.Span("ragged.put", 0.010, 0.020, {"prefill": 0, "decode": 5}, None),
             pt.Span("ragged.put", 0.030, 0.040, {"prefill": 0, "decode": 3}, None),
             pt.Span("ragged.put", 0.050, 0.090, {"prefill": 70, "decode": 2}, None)]
    L = J + "linear_attn/"
    ops = [pt.Op("fusion.1", 0.011, 0.012, L + "dot_general:"),
           pt.Op("fusion.2", 0.012, 0.0125, L + "conv/mul:"),
           pt.Op("fusion.3", 0.013, 0.015, L + "delta_step/mul:"),
           pt.Op("fusion.4", 0.015, 0.016, J + "jvp(linear_attn)/delta_step/add:"),
           pt.Op("while.5", 0.016, 0.018, L + "delta_chunk/while:"),
           pt.Op("fusion.6", 0.016, 0.017, L + "delta_chunk/while/body/dot_general:"),
           pt.Op("fusion.7", 0.018, 0.019, J + "attn/dot_general:"),
           pt.Op("fusion.3", 0.031, 0.032, L + "delta_step/mul:"),
           pt.Op("fusion.3", 0.051, 0.060, L + "delta_step/mul:")]
    cfg = {"layer_types": ["linear_attention"] * 3 + ["full_attention"],
           "linear_num_value_heads": 2, "linear_key_head_dim": 4,
           "linear_value_head_dim": 8}
    cell = type("Cell", (), {"config": cfg})()
    return {"program_trace": pt.ProgramTrace(spans, {0: ops}),
            "window": (0.0, 0.1), "cell": cell, "n_layers": 4,
            "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}}


def test_named_scope_reader_on_a_hand_built_trace():
    record = _hand_record()
    spec = json.load(open(os.path.join(
        harness.HERE, "metrics", "linear_attn_device_ms.serve.json")))
    # tick 1: 1 + 0.5 + 2 + 1 + 1 (the while itself left out) = 5.5 ms;
    # tick 2: 1 ms; the prefill tick is not read: the median is 3.25
    assert named_scope_device.read(record, dict(spec["args"])) \
        == pytest.approx(3.25)
    assert named_scope_device.read(
        record, {"span": "ragged.put",
                 "scope": ["linear_attn", "delta_step"]}) == pytest.approx(2.0)


def test_roofline_reader_on_a_hand_built_trace():
    record = _hand_record()
    spec = json.load(open(os.path.join(
        harness.HERE, "metrics", "delta_step_roofline_pct.json")))
    # 8 decoded sequences x 3 linear layers; by bytes at 1 GB/s; 4 ms read
    _, moved = gated_delta.ops_and_bytes(8, 2, 4, 8)
    want = 100.0 * (3 * moved / 1e9) / 0.004
    assert gated_delta_roofline.read(record, dict(spec["args"])) \
        == pytest.approx(want)


@pytest.mark.parametrize("reader,args", [
    (named_scope_device, {"span": "ragged.put", "scope": ["linear_attn"]}),
    (gated_delta_roofline, {"span": "ragged.put",
                            "scope": ["linear_attn", "delta_step"]})])
def test_readers_find_nothing_in_a_program_without_the_scopes(reader, args):
    """The parent of PR 28: spans, but no operation under the scope."""
    record = _hand_record()
    ops = [o for o in record["program_trace"].ops[0]
           if "linear_attn" not in o.op_name]
    record["program_trace"] = pt.ProgramTrace(
        record["program_trace"].spans, {0: ops})
    assert reader.read(record, args) is None
