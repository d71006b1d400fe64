"""The cell ``a.x-k1.docs``: the configuration as the issue cut it, the
traffic's rule (shared documents, one schedule for every seed), the
operation and byte counts of latent attention, the new readers on a small
hand-made trace, and the cell end to end at a size the CPU holds."""

import importlib
import importlib.util
import json
import os
import types

import numpy as np
import pytest

from benchmarks import harness, program_trace as pt
from benchmarks.generators import shared_docs_ruled as gen
from benchmarks.ops_bytes import latent_attention as ob
from benchmarks.readers import (latent_attention_roofline, named_scope_device,
                                span_attr_ratio)

_spec = importlib.util.spec_from_file_location(
    "bench_conftest", os.path.join(os.path.dirname(__file__), "conftest.py"))
bench_conftest = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench_conftest)   # make_tiny_root, not a fixture
_edit, make_tiny_root = bench_conftest._edit, bench_conftest.make_tiny_root

CELL = "a.x-k1.docs"
SEED = 4_900_000_049
MIX = json.load(open(os.path.join(harness.HERE, "traffic", "docs.json")))
BENCH = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
TINY_MLA = {"q_lora_rank": 48, "kv_lora_rank": 128, "qk_nope_head_dim": 32,
            "qk_rope_head_dim": 64, "v_head_dim": 32,
            "moe_intermediate_size": 32, "num_experts_per_tok": 4}
TINY_DOCS = {"documents": {"count": 4, "dist": "loguniform", "min": 32,
                           "max": 64, "round_to": 16},
             "question_tokens": {"dist": "lognormal", "median": 6,
                                 "sigma": 0.5, "min": 3, "max": 12},
             "prompt_tokens": {"dist": "loguniform", "min": 35, "max": 76},
             "output_tokens": {"dist": "lognormal", "median": 4,
                               "sigma": 0.5, "min": 2, "max": 6},
             "publish": {"every_seconds": 0.25, "answer_tokens": 2},
             "lead_seconds": 2, "steady_lead_seconds": 1, "grace_seconds": 30}


# ----------------------------------------------------------------------
# the configuration and the mix are the issue's
def test_the_entry_keeps_the_contract_but_for_the_vocabulary():
    """``test_benchmark_contract.py::test_configuration_entry[a.x-k1]`` is
    marked xfail for ONE of its lines (``vocab_size`` in ``reduced`` holds
    the letters "size"; ``tests/conftest.py`` says why): every line of it
    is asserted here, that one with the vocabulary's slice set apart."""
    entry = next(c for c in BENCH["configs"] if c["name"] == "a.x-k1")
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert len(entry["why"]) <= 200 and entry["source"].startswith("https://")
    data = json.load(open(os.path.join(harness.ROOT, entry["file"])))
    assert data["source"] == entry["source"]
    assert data["reduced"] == entry["reduced"]
    widths = ("size", "_dim", "_rank", "per_tok", "head")
    assert [k for k in entry["reduced"] if any(w in k for w in widths)] \
        == ["vocab_size"]
    assert any(w["config"] == "a.x-k1" for w in BENCH["workloads"])
    for part in ("architectures", "reference"):
        importlib.import_module(f"benchmarks.{part}.{data['architecture']}")


def test_the_configuration_is_the_issues_cut():
    cell = harness.Cell(CELL)
    cfg = cell.config
    assert cell.spec["runner"] == "serve_open_loop" and cell.chips == 1
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 61,
                                "n_routed_experts": 192, "vocab_size": 163840}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["experts_held"]) == (6, 12, 20480, [0, 12])
    # every number of the catalog's config, under the same key
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_attention_heads"]) \
        == (7168, 18432, 2048, 64)
    assert (cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"]) \
        == (1536, 512, 128, 64, 128)
    assert (cfg["n_group"], cfg["topk_group"], cfg["num_experts_per_tok"],
            cfg["scoring_func"], cfg["routed_scaling_factor"],
            cfg["topk_method"]) == (8, 4, 8, "sigmoid", 2.5, "none")
    assert cfg["rope_scaling"]["factor"] == 32
    assert set(cfg["assumed"]) >= {"topk_method", "weight_names", "decoding",
                                   "attention_form"}
    e = cfg["engine"]
    assert (e["token_budget"], e["max_seqs"], e["kv_block_size"],
            e["max_context"], e["max_kv_blocks"], e["enable_prefix_cache"]) \
        == (1024, 64, 16, 16384, 32768, True)
    # the cell is appended to what every serving cell reports, and to the
    # experts' device time; nothing of the paged K/V kernel's
    mine = {m["name"] for m in cell.metrics("per_layer")}
    assert {"latent_attn_device_ms.serve", "latent_attn_roofline_pct",
            "experts_touched_pct", "prefix_hit_pct",
            "experts_device_ms.serve", "attn_device_ms.serve",
            "put_decode_ms", "gap_ms"} <= mine
    assert "paged_attn_roofline_pct" not in mine
    assert {m["name"] for m in cell.metrics("end_to_end")} \
        == {"itl_p50_ms", "setup_s"}


def test_the_mix_is_the_issues():
    assert MIX["generator"] == "shared_docs_ruled" and MIX["order_seed"] == 49
    assert MIX["documents"] == {"count": 16, "dist": "loguniform",
                                "min": 4096, "max": 15360, "round_to": 16}
    assert MIX["question_tokens"] == {"dist": "lognormal", "median": 96,
                                      "sigma": 0.6, "min": 32, "max": 256}
    assert MIX["output_tokens"] == {"dist": "lognormal", "median": 256,
                                    "sigma": 0.5, "min": 64, "max": 512}
    assert (MIX["prompt_tokens"]["min"], MIX["prompt_tokens"]["max"]) \
        == (4096 + 32, 15360 + 256)
    assert (MIX["lead_seconds"], MIX["steady_lead_seconds"],
            MIX["grace_seconds"]) == (40, 20, 30)
    assert MIX["publish"] == {"every_seconds": 1.0, "answer_tokens": 16}
    docs = gen.document_lengths(MIX)
    assert len(docs) == 16 and all(n % 16 == 0 for n in docs)
    assert 4096 <= min(docs) and max(docs) <= 15360
    assert 130_000 < sum(docs) < 140_000 and 7600 < np.median(docs) < 8300
    # the longest document, question and answer fit the engine's context
    e = harness.Cell(CELL).config["engine"]
    assert max(docs) + 256 + 512 <= e["max_context"]
    assert gen.quantile(MIX["prompt_tokens"], 0.0) == 4128
    assert gen.quantile(MIX["prompt_tokens"], 1.0) == pytest.approx(15616)
    assert gen.quantile(MIX["question_tokens"], 0.5) == pytest.approx(96)


def test_the_schedule_is_one_for_every_seed_and_documents_take_turns():
    a = gen.generate(MIX, 7.0, 30.0, 1, 20480)
    b = gen.generate(MIX, 7.0, 30.0, SEED, 20480)
    assert [(x.due, len(x.prompt), x.max_new_tokens) for x in a] \
        == [(x.due, len(x.prompt), x.max_new_tokens) for x in b]
    assert a[0].prompt != b[0].prompt                # the seed's: token ids
    assert max(max(x.prompt) for x in b[:20]) < 20480
    rule = gen.schedule(MIX, 7.0, 30.0)
    first, steady = rule[:16], rule[16:]
    # the lead-in: one first question a document, a second apart, 16 tokens
    assert [r[0] for r in first] == [-40.0 + i for i in range(16)]
    assert sorted(r[1] for r in first) == list(range(16))
    assert {r[3] for r in first} == {16}
    # then the steady mix from -20 s: round robin in the rule's permutation
    assert steady[0][0] == -20.0 and len(steady) == 140 + 210
    turn = [r[1] for r in first]
    assert [r[1] for r in steady] == [turn[i % 16] for i in range(len(steady))]
    inside = [r for r in steady if r[0] >= 0]
    assert len(inside) == 210 and inside[0][0] == 0.0
    counts = np.bincount([r[1] for r in inside], minlength=16)
    assert counts.min() >= 13 and counts.max() <= 14
    # a request is its document followed by a question of its own
    docs = gen.document_lengths(MIX)
    for x, (_, d, q, out) in zip(b, rule):
        assert len(x.prompt) == docs[d] + q and x.max_new_tokens == out
        assert 32 <= q <= 256
    same = [x for x, r in zip(b, rule) if r[1] == turn[0]]
    n = docs[turn[0]]
    assert all(x.prompt[:n] == same[0].prompt[:n] for x in same)
    assert same[0].prompt[n:n + 8] != same[1].prompt[n:n + 8]
    assert {64 <= r[3] <= 512 for r in steady} == {True}


# ----------------------------------------------------------------------
# operations and bytes, and the readers
def test_latent_attention_ops_and_bytes():
    # 50 sequences of 8,192 tokens each decode one: rows x 1,152 B, and 121
    # operations a byte of the rows
    flops, moved = ob.ops_and_bytes(50 * 8192, 50, 64, 576, 512)
    rows = 50 * 8192
    assert flops == 2.0 * rows * 64 * (576 + 512)
    assert moved == rows * 1152 + 50 * 64 * (576 + 512) * 2
    assert 120 < flops / (rows * 1152) < 122
    assert ob.ops_and_bytes(0, 0, 64, 576, 512) == (0.0, 0.0)


def _program(put_attrs, ops):
    spans = [pt.Span("ragged.put", float(i), float(i) + 0.9, dict(a), None)
             for i, a in enumerate(put_attrs)]
    return pt.ProgramTrace(spans, {0: ops})


def test_readers_read_the_program_record():
    cell = harness.Cell(CELL)
    attrs = [{"prefill": 0, "decode": 50, "ctx_rows": 400_000,
              "latent_layers": 6, "experts_touched": 45, "experts_held": 60,
              "matched": 0, "prompt": 0},
             {"prefill": 96, "decode": 49, "ctx_rows": 408_000,
              "latent_layers": 6, "experts_touched": 60, "experts_held": 60,
              "matched": 8000, "prompt": 8096},
             {"prefill": 0, "decode": 50, "ctx_rows": 400_000,
              "latent_layers": 6, "experts_touched": 51, "experts_held": 60,
              "matched": 0, "prompt": 0}]
    op = lambda name, path, a, b: types.SimpleNamespace(
        name=name, op_name=path, start=a, end=b)
    ops = [op("latent_attention.1", "jit(step)/attn/latent/pallas_call", 0.1, 0.104),
           op("fusion.2", "jit(step)/attn/absorb/dot_general", 0.2, 0.3),
           op("latent_attention.1", "jit(step)/attn/latent/pallas_call", 1.1, 1.2),
           op("latent_attention.1", "jit(step)/attn/latent/pallas_call", 2.1, 2.108)]
    record = {"window": (0.0, 3.0), "program_trace": _program(attrs, ops),
              "cell": cell, "n_layers": 6,
              "peaks": harness.peaks_of("TPU v5 lite")}
    spec = lambda m: cell.metric_spec(m)["args"]
    # the two ticks without a prompt chunk: 4 and 8 ms under attn/latent
    assert named_scope_device.read(
        record, spec("latent_attn_device_ms.serve")) == pytest.approx(6.0)
    least = 2 * 6 * (400_000 * 1152 + 50 * 64 * 1088 * 2) / 819e9
    assert latent_attention_roofline.read(
        record, spec("latent_attn_roofline_pct")) \
        == pytest.approx(100 * least / 0.012, rel=1e-6)
    assert span_attr_ratio.read(record, spec("experts_touched_pct")) \
        == pytest.approx(100 * 96 / 120)
    assert span_attr_ratio.read(record, spec("prefix_hit_pct")) \
        == pytest.approx(100 * 8000 / 8096)
    # a program without the scope and the attributes (the parent): nothing
    record["program_trace"] = _program([{"prefill": 0, "decode": 50}], ops[1:2])
    for m in ("latent_attn_device_ms.serve", "latent_attn_roofline_pct",
              "experts_touched_pct", "prefix_hit_pct"):
        reader = harness.find("readers", cell.metric_spec(m)["reader"])
        assert reader.read(record, spec(m)) is None, m


def test_roofline_share_states_its_ceiling():
    spec = harness.Cell(CELL).metric_spec("latent_attn_roofline_pct")
    assert "90%" in spec["what"] and "640" in spec["what"]


# ----------------------------------------------------------------------
# the cell, cut to the CPU's size
@pytest.fixture(scope="module")
def axk1_root(tmp_path_factory):
    """``make_tiny_root`` knows nothing of the latent ranks, the experts'
    keys or of ``docs``: cut those here."""
    root = make_tiny_root(str(tmp_path_factory.mktemp("axk1")))
    b = os.path.join(root, "benchmarks")

    def cut(c):
        c.update(TINY_MLA)
        c["rope_scaling"]["original_max_position_embeddings"] = 64
    _edit(os.path.join(b, "configs", "a.x-k1.json"), cut)
    _edit(os.path.join(b, "traffic", "docs.json"),
          lambda t: t.update(TINY_DOCS))
    _edit(os.path.join(b, "workloads", CELL + ".json"),
          lambda w: w["check"].update(max_prompt_tokens=76, reference_tokens=80,
                                      limits={"logit_err_median": 0.05,
                                              "logit_err_max": 0.5}))
    return root


def test_adapter_builds_from_a_file_cut_by_make_tiny_root(tiny_root):
    cell = harness.Cell(CELL, root=tiny_root)
    c = harness.find("architectures", "axk1").build(
        cell.config, cell.n_layers).config
    assert (c.d_model, c.n_heads, c.n_layers, c.vocab_size) == (64, 4, 2, 256)
    assert (c.n_experts, c.n_held, c.top_k) == (192, 12, 8)
    assert (c.kv_lora_rank, c.latent_row, c.first_dense_layers) == (512, 640, 1)


def test_cell_runs_end_to_end_at_a_tiny_size(axk1_root, run_cell):
    rc, last, out = run_cell(axk1_root, "--workload", CELL, "--seed",
                             str(SEED), "--seconds", "2", "--trace", "0")
    assert rc == 0, out[-3000:]
    assert set(last["metrics"]) == {"itl_p50_ms", "setup_s"}
    assert all(m["value"] > 0 for m in last["metrics"].values())
    assert last["attempted"] > 0 and last["failed"] == 0
    checks = [l for l in out.splitlines() if l.startswith("check ")]
    precision = [l for l in checks if "_err" in l]
    assert len(precision) == 2 and all(l.endswith(" ok") for l in precision), \
        checks
    assert any("kernel_missing" in l and "OUTSIDE" in l for l in checks)
    assert last["correct"] is False          # no Pallas kernel on the CPU
    assert "compiled_in_window: 0 " in out and "undrained: 0 " in out
