"""BENCHMARK.json against the contract's limits, and every cell's files."""

import json
import re

import pytest
from conftest import BENCH, TINY
from harness import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module", params=["BENCHMARK.json", "tests/BENCHMARK.tiny.json"])
def bench(request):
    path = BENCH.parent / request.param if request.param == "BENCHMARK.json" else BENCH / request.param
    return json.loads(path.read_text())


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"]
    assert 1 <= bench["run_seconds"] <= 51 and isinstance(bench["run_seconds"], int)
    assert all(not w.startswith("/") and ".." not in w for w in bench["command"])


def test_names_units_and_lines(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0 < m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e and "\n" not in m["layer"] and len(m["layer"]) <= 200
    for entry in bench["configs"] + bench["workloads"]:
        assert NAME.match(entry["name"])
        assert 1 <= len(entry["why"]) <= 200 and "\t" not in entry["why"]
    assert all(NAME.match(k) for c in bench["configs"] for k in c["reduced"])
    assert all(NAME.match(w["traffic"]) and w["chips"] in (1, 4) for w in bench["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_cell_has_its_files(bench):
    used = set()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], bench)
        cfg = cell["config"]
        used.add(w["config"])
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (BENCH / "drivers" / f"{cfg['entry']}.py").is_file()
        assert (BENCH / "reference" / f"{cfg['reference']}.py").is_file()
        assert (BENCH / "flops" / f"{cfg['flops']}.py").is_file()
        comparison = spec.load_module("reference", cfg["reference"]).COMPARISON
        assert (BENCH / "comparisons" / f"{comparison}.py").is_file()
        generator = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())["generator"]
        assert (BENCH / "generators" / f"{generator}.py").is_file()
        assert cfg["limits"], "a cell compares at least one number"
        assert cell["per_layer"], "a cell reports at least one per-layer metric"
        assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s", "train_functions_per_s"}
        # a metric's data file (reader, args) says the same as its entry
        for kind, keys in (("per_layer", ("unit", "better", "source", "layer", "moves")),
                           ("end_to_end", ("unit", "better", "source"))):
            for m in cell[kind]:
                assert (BENCH / "readers" / f"{m['reader']}.py").is_file()
                listed = next(p for p in bench[kind] if p["name"] == m["name"])
                for key in keys:
                    assert m[key] == listed[key], (m["name"], key)
    assert used == {c["name"] for c in bench["configs"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files)) and all(f.startswith("benchmark/") for f in files)


def test_reduced_names_no_width():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    widths = ("hidden_size", "intermediate_size", "head", "_dim", "_rank")
    for c in bench["configs"]:
        cfg = json.loads((BENCH.parent / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
        assert not [k for k in c["reduced"] if any(w in k for w in widths)]
        # the published codebert-base config.json, key for key, but for `reduced`
        published = {"vocab_size": 50265, "hidden_size": 768, "num_hidden_layers": 12,
                     "num_attention_heads": 12, "intermediate_size": 3072,
                     "max_position_embeddings": 514, "type_vocab_size": 1,
                     "layer_norm_eps": 1e-05, "pad_token_id": 1,
                     "hidden_dropout_prob": 0.1, "attention_probs_dropout_prob": 0.1}
        differs = {k for k, v in published.items() if cfg["model"][k] != v}
        assert differs == set(c["reduced"])


def test_tiny_file_is_where_the_tests_say():
    assert json.loads(open(TINY).read())["workloads"]
