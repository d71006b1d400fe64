"""``BENCHMARK.json`` against the contract, and every file a name leads to."""

import importlib
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
CELLS = {w["name"]: w for w in BENCH["workloads"]}


def _bench_file(*parts):
    return os.path.join(ROOT, "benchmarks", *parts)


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks", "tests/benchmark"]
    assert BENCH["command"][-1] == "benchmarks/run.py"
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.1


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_entry(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and len(cfg["why"]) <= 200
    assert cfg["source"].startswith("https://")
    data = json.load(open(os.path.join(ROOT, cfg["file"])))
    assert data["source"] == cfg["source"] and data["reduced"] == cfg["reduced"]
    widths = ("size", "_dim", "_rank", "per_tok", "head")
    assert not [k for k in cfg["reduced"] if any(w in k for w in widths)]
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])
    importlib.import_module(f"benchmarks.architectures.{data['architecture']}")
    importlib.import_module(f"benchmarks.reference.{data['architecture']}")


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_its_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    from benchmarks import harness

    c = harness.Cell(cell["name"])      # checks the files against the entry
    importlib.import_module(f"benchmarks.runners.{c.spec['runner']}")
    importlib.import_module(f"benchmarks.generators.{c.traffic['generator']}")
    reported = [m["name"] for m in c.metrics("end_to_end")]
    assert "setup_s" in reported and len(reported) >= 2
    assert c.metrics("per_layer")
    assert sum(cell["traffic"] == w["traffic"] and cell["config"] == w["config"]
               for w in BENCH["workloads"]) == 1


@pytest.mark.parametrize("m", BENCH["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(m):
    assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.1
    assert set(m.get("workloads", [])) <= set(CELLS)


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_and_its_reader(m):
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    moved = E2E[m["moves"]]
    for cell in m["workloads"]:       # each has to report what it moves
        assert cell in moved.get("workloads", list(CELLS))
    spec = json.load(open(_bench_file("metrics", m["name"] + ".json")))
    assert spec["layer"] == m["layer"] and spec["unit"] == m["unit"]
    assert spec["moves"] == m["moves"]
    reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
    assert callable(reader.read)
    if "roofline" in m["name"] or "mfu" in m["name"]:
        assert m["unit"] == "%"


def test_names_are_unique_and_files_are_named_from_name_characters():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in BENCH[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for base in BENCH["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, base)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(d, f)


def test_peaks_table_has_its_source_and_the_v5e():
    from benchmarks import harness

    peaks = harness.peaks_of("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9 and peaks["ici_bits_per_s"] == 1600e9
    with pytest.raises(harness.BenchError):
        harness.peaks_of("TPU v9 imaginary")
