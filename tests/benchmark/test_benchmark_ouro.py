"""The cell ``ouro-2.6b.think`` at a size the CPU holds: end to end through
the command; the program against ``reference/ouro.py`` through the paged
cache of every pass, and the int8 control and an int8 program told apart;
the configuration file against the catalog's ``config``; and the reader PR 35
brought (``loop_paged_attention_roofline``) with the row writes under the
loop (``attn/scatter``, ``loop_device_ms.serve``) under
``named_scope_device``, on a hand-built trace and on a program without the
attribute or the loop; the mix ``think`` under ``open_loop``."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness, program_trace as pt, trace_reduce as tr
from benchmarks.ops_bytes import paged_attention
from benchmarks.readers import (loop_paged_attention_roofline,
                                named_scope_device)
from benchmarks.reference import mistral as ref_mistral
from benchmarks.runners import serve_open_loop as serve

_spec = importlib.util.spec_from_file_location(
    "bench_conftest", os.path.join(os.path.dirname(__file__), "conftest.py"))
bench_conftest = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench_conftest)   # make_tiny_root, not a fixture
_edit, make_tiny_root = bench_conftest._edit, bench_conftest.make_tiny_root

CELL = "ouro-2.6b.think"
SEED = 3_500_000_023
TINY_THINK = {"prompt_tokens": {"dist": "lognormal", "median": 24,
                                "sigma": 0.5, "min": 8, "max": 48},
              "output_tokens": {"dist": "lognormal", "median": 8,
                                "sigma": 0.5, "min": 4, "max": 12},
              "lead_seconds": 1, "grace_seconds": 30}


@pytest.fixture(scope="module")
def ouro_root(tmp_path_factory):
    """``make_tiny_root`` knows nothing of ``think``: cut it here. The
    tiny configuration keeps its four passes (and, cut to two layers of
    two KV heads, is grouped-query, which the published model is not)."""
    root = make_tiny_root(str(tmp_path_factory.mktemp("ouro")))
    b = os.path.join(root, "benchmarks")
    _edit(os.path.join(b, "traffic", "think.json"),
          lambda t: t.update(TINY_THINK))
    # bfloat16 on the CPU at width 64 is coarser than at 2048 on the chip
    _edit(os.path.join(b, "workloads", CELL + ".json"),
          lambda w: w["check"].update(limits={"logit_err_median": 0.03,
                                              "logit_err_max": 0.5}))
    return root


def test_configuration_file_holds_the_catalog_config():
    """Every key of the catalog's ``config`` under the same key, nothing
    reduced; the adapter builds the published model from it."""
    cfg = json.load(open(os.path.join(harness.HERE, "configs",
                                      "ouro-2.6b.json")))
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        row = next(json.loads(l) for l in open(path) if '"Ouro-2.6B"' in l)
        assert cfg["source"] == row["source_url"]
        assert {k: cfg[k] for k in row["config"]} == row["config"]
    assert cfg["reduced"] == [] and cfg["total_ut_steps"] == 4
    assert len(cfg["assumed"]) == 6
    c = harness.find("architectures", "ouro").build(
        cfg, cfg["num_hidden_layers"]).config
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff) \
        == (48, 2048, 16, 16, 5632)
    assert c.total_ut_steps == 4 and c.sandwich_norm
    assert round(c.param_count() / 1e9, 3) == 2.668


def test_cell_runs_end_to_end_at_a_tiny_size(ouro_root, run_cell):
    rc, last, out = run_cell(ouro_root, "--workload", CELL, "--seed",
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
    assert "passes=4" in out                 # the engine's construction line


@pytest.fixture(scope="module")
def served(ouro_root):
    cell = harness.Cell(CELL, root=ouro_root)
    model, params, engine = serve.build_engine(cell, SEED)
    prompts = serve.check_prompts(cell, SEED)
    steps = cell.spec["check"]["decode_steps"]
    fed, got = serve.engine_logits(engine, prompts, steps)
    want = serve.reference_logits(cell, params, fed,
                                  [len(p) for p in prompts], steps)
    return cell, model, params, engine, prompts, fed, got, want


def test_reference_agrees_through_every_pass_cache(served):
    cell, model, _, engine, prompts, fed, got, want = served
    assert model.config.total_ut_steps == 4
    pages = engine.config.n_kv_blocks + 1
    assert [k.shape[0] for k in engine.kv_pool.k] == [4 * pages] * 2
    err = serve.position_errors(got, want)
    steps = cell.spec["check"]["decode_steps"]
    assert err.size == len(prompts) * (steps + 1)
    # bfloat16 at width 64 on the CPU (float32: tests/test_ouro.py)
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


# ----------------------------------------------------------------------
# the readers, on a hand-built trace
J = "jit(step)/while/body/closed_call/"
KERNEL = tr.clean(   # the event's name as trace_reduce keeps it
    "%paged_attention.7 = bf16[16,1,64,128]{3,2,1,0:T(8,128)(2,1)S(1)} "
    "custom-call(%a, %b), custom_call_target=\"tpu_custom_call\"")


def _hand_record(kv_layers=6):
    """Two decode-only ticks and one with a prompt chunk, each a wrapper
    call around a ``ragged.put`` span; in the first tick six kernel calls
    of 1 ms (3 layers x 2 passes), the row writes of the carried leaves
    (``attn/scatter`` under the loop) and the norm between passes under
    ``loop``."""
    attrs = lambda prefill, decode: dict(
        {"prefill": prefill, "decode": decode, "passes": 2},
        **({"kv_layers": kv_layers} if kv_layers else {}))
    spans = [pt.Span("ragged.put", 0.010, 0.020, attrs(0, 2), None),
             pt.Span("ragged.put", 0.030, 0.040, attrs(0, 2), None),
             pt.Span("ragged.put", 0.050, 0.090, attrs(70, 2), None)]
    ops = [pt.Op("fusion.1", 0.0110, 0.0112, J + "attn/scatter:"),
           pt.Op("fusion.2", 0.0112, 0.0113, J + "attn/scatter:"),
           pt.Op("while.3", 0.0100, 0.0190, "jit(step)/while:"),
           pt.Op("fusion.4", 0.0120, 0.0130, J + "attn/dot_general:"),
           pt.Op("fusion.5", 0.0130, 0.0131, J + "loop/reduce_sum:"),
           pt.Op("fusion.1", 0.0310, 0.0313, J + "attn/scatter:")]
    calls = [{"t0": 0.009, "t1": 0.021, "seqs": [(1, 100), (1, 40)]},
             {"t0": 0.029, "t1": 0.041, "seqs": [(1, 101), (1, 41)]},
             {"t0": 0.049, "t1": 0.091, "seqs": [(70, 70), (1, 102), (1, 42)]}]
    kernel = [(KERNEL, 0.012 + i * 1e-3, 0.013 + i * 1e-3) for i in range(6)]
    cfg = {"num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16}
    cell = type("Cell", (), {"config": cfg})()
    return {"program_trace": pt.ProgramTrace(spans, {0: ops}),
            "trace": tr.Trace(device_ops={0: kernel}),
            "window": (0.0, 0.1), "to_trace": 0.0, "calls": calls,
            "cell": cell, "n_layers": 3,
            "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}}


def test_row_writes_under_the_loop_are_read_by_their_path():
    spec = json.load(open(os.path.join(
        harness.HERE, "metrics", "loop_device_ms.serve.json")))
    assert spec["args"]["scope"] == ["attn", "scatter"]
    # tick 1: 0.2 + 0.1 ms (the while itself, the products and the norm
    # left out); tick 2: 0.3 ms
    assert named_scope_device.read(_hand_record(), dict(spec["args"])) \
        == pytest.approx(0.3)


def test_roofline_reader_counts_the_kernel_calls_from_the_span():
    record = _hand_record()
    spec = json.load(open(os.path.join(
        harness.HERE, "metrics", "loop_paged_attn_roofline_pct.json")))
    moved = sum(paged_attention.ops_and_bytes(c["seqs"], 4, 4, 16)[1]
                for c in record["calls"])
    # six calls a tick, not the cell's three layers; by bytes at 1 GB/s
    want = 100.0 * (6 * moved / 1e9) / 0.006
    assert loop_paged_attention_roofline.read(record, dict(spec["args"])) \
        == pytest.approx(want)
    assert record["n_layers"] == 3           # the record is not edited


def test_readers_find_nothing_in_a_program_without_what_they_read():
    """Spans without ``kv_layers`` (the parent of PR 35); a step whose row
    writes carry no scope (PR 35's first form of the loop)."""
    record = _hand_record(kv_layers=0)
    spec = json.load(open(os.path.join(
        harness.HERE, "metrics", "loop_paged_attn_roofline_pct.json")))
    assert loop_paged_attention_roofline.read(
        record, dict(spec["args"])) is None
    ops = [o for o in record["program_trace"].ops[0]
           if "attn/scatter" not in o.op_name]
    record["program_trace"] = pt.ProgramTrace(
        record["program_trace"].spans, {0: ops})
    spec = json.load(open(os.path.join(
        harness.HERE, "metrics", "loop_device_ms.serve.json")))
    assert named_scope_device.read(record, dict(spec["args"])) is None


def test_every_seed_offers_the_mix_in_an_order_of_its_own():
    """The mix ``think`` under ``open_loop`` at the cell's rate: every seed
    the same lengths and gaps, in another order, with its own token ids;
    ``correct``'s prompts are drawn from the seed, not from the order."""
    from benchmarks.generators import open_loop as gen

    mix = json.load(open(os.path.join(harness.HERE, "traffic", "think.json")))
    spec = json.load(open(os.path.join(harness.HERE, "workloads",
                                       CELL + ".json")))
    assert mix["generator"] == "open_loop" and "order_seed" not in mix
    rate = spec["rate_per_s"]
    a, b, again = (gen.generate(mix, rate, 30.0, s, 49152)
                   for s in (SEED, SEED + 1, SEED))
    plan = lambda xs: [(x.due, len(x.prompt), x.max_new_tokens) for x in xs]
    inside = lambda xs: [x for x in xs if x.due >= 0]
    assert len(inside(a)) == len(inside(b)) == round(rate * 30)
    assert plan(a) == plan(again) and plan(a) != plan(b)
    for part in (inside, lambda xs: [x for x in xs if x.due < 0]):
        assert sorted(len(x.prompt) for x in part(a)) \
            == sorted(len(x.prompt) for x in part(b))
        assert sorted(x.max_new_tokens for x in part(a)) \
            == sorted(x.max_new_tokens for x in part(b))
    assert all(32 <= len(x.prompt) <= 384 and 48 <= x.max_new_tokens <= 320
               for x in a)
    assert [x.prompt for x in a] == [x.prompt for x in again]
    cell = harness.Cell(CELL)
    lens = lambda s: [len(p) for p in serve.check_prompts(cell, s)]
    assert lens(SEED) == lens(SEED) != lens(SEED + 1)
