"""The third routed frozen-decoder configuration's tiny twin (beside
``test_longcat_frozen.py`` and ``test_pangu_frozen.py``): ``run.py`` end to
end on the CPU with the routing and attention metrics, the appended cell and
the new metrics through ``spec.load_cell`` by name, two plantings that read
``correct: false``, the FLOP count against a hand count, and the driver's
refusal of a mix whose checked rows all fit inside the window. The model
against the reference, every planting and the halves of the expert layer are
tier-1 cases (``tests/test_smallthinker.py``)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest
from conftest import BENCH

ROOT = BENCH.parent
TINY = str(BENCH / "tests" / "BENCHMARK.smallthinker.tiny.json")
CELL = "tiny-smallthinker-21b-msivd.joint"
REAL = "smallthinker-21b-msivd.joint-8k"
ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "TF_CPP_MIN_LOG_LEVEL": "3",
       "JAX_COMPILATION_CACHE_DIR": ""}
ROUTED = {"moe_tokens_per_expert.train", "moe_load_max_over_mean.train", "moe_dropped.train",
          "moe_held_share.train"}
NEW = {"attn_needed_share.train", "moe_gathered_share.train"}
LAYER = "routed decoder (llm/smallthinker.py, ops/grouped.py, ops/ring_attention.py)"


@pytest.mark.parametrize("trace", [0, 1])
def test_well_formed_last_line_with_the_routing_and_attention_metrics(trace, tmp_path):
    env = {**ENV, "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"), "TMPDIR": str(tmp_path)}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", str(2**31 + 7),
         "--seconds", "1", "--trace", str(trace), "--benchmark-file", TINY],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    assert row["correct"] is True and row["failed"] == 0 and row["attempted"] > 0
    assert set(row["compared"]) == {"grad1_gap", "delta_gap", "hidden_gap", "route_gap",
                                    "step_logit_gap", "step_count_gap"}
    assert row["observed"]["route_agree_share"] > 0.99
    if trace:  # no device metric off the TPU; the program's counters are exact anywhere
        m = row["metrics"]
        assert ROUTED | NEW | {"ggnn_fill.train", "pad_share_tokens.train"} <= set(m)
        assert not {"moe_zero_share.train", "moe_combine_fill.train",
                    "latent_attn_fused_share.train"} & set(m)
        assert m["moe_dropped.train"]["value"] == 0
        assert m["moe_held_share.train"]["value"] == 100  # every expert is here
        assert m["moe_gathered_share.train"]["value"] == 100  # and the gather combines them all
        assert 5 < m["attn_needed_share.train"]["value"] < 60  # pads and the blocks' corners
        assert m["moe_load_max_over_mean.train"]["value"] >= 1
    else:
        assert set(row["metrics"]) == {"train_functions_per_s", "setup_s"}


def test_the_appended_cell_and_the_new_metrics_resolve_by_name():
    from harness import spec, traffic

    bench = spec.load_benchmark()
    cell = spec.load_cell(REAL, bench)
    assert cell["cell"]["chips"] == 1
    assert cell["cell"]["traffic"] == "precisebugs-text-graphs-8k-v151936"
    cfg = cell["config"]
    assert (cfg["entry"], cfg["reference"], cfg["flops"]) == (
        "joint_trainer_frozen_smallthinker", "smallthinker_fusion", "smallthinker_fusion_train")
    names = {m["name"] for m in cell["per_layer"]}
    pangu = {m["name"] for m in spec.load_cell("openpangu-ultra-msivd.joint-2k", bench)["per_layer"]}
    # what a decoder with latent attention and a one-hot combine reports and this one does not
    assert pangu - names == {"latent_attn_fused_share.train", "moe_combine_fill.train"}
    assert names - pangu == NEW
    by_name = {m["name"]: m for m in cell["per_layer"]}
    for name, num, den in (("attn_needed_share.train", "attn_pairs_needed", "attn_pairs_computed"),
                           ("moe_gathered_share.train", "moe_gathered", "moe_held")):
        m = by_name[name]
        assert m["reader"] == "program_attr_quotient" and m["workloads"] == [REAL]
        assert m["args"] == {"span": "loss.sync", "num": [num], "den": [den], "scale": 100.0}
        assert (m["layer"], m["unit"], m["better"], m["moves"]) == (
            LAYER, "%", "higher", "train_functions_per_s")
    assert by_name["moe_held_share.train"]["workloads"][-1] == REAL
    assert {m["name"] for m in cell["end_to_end"]} == {"train_functions_per_s", "setup_s"}
    assert set(cfg["limits"]) == set(cfg["limit_reasons"]) - {"expert_gap"}
    # the traffic holds the parameters the cell states
    joined = traffic.load_mix(cell["cell"]["traffic"])
    text = traffic.load_mix(joined["text"])
    assert (joined["generator"], joined["graphs"], joined["n_examples"]) == (
        "text_graphs", "bigvul-graphs", 4096)
    assert (text["block"], text["vocab"], text["n_examples"], text["positive_rate"]) == (
        8192, 151936, 4096, 0.06)
    assert text["length"] == {"parts": [{"share": 1.0, "dist": "lognormal", "median": 2800,
                                         "sigma": 0.9}], "min": 64, "max": 8192}
    assert text["block"] == cfg["train"]["block_size"] and text["vocab"] == cfg["vocab_size"]
    # the three checked batches: no vulnerable function, and a row past the window in each
    lengths = traffic.sizes(text["length"], 4096, text["size_seed"])
    order = np.arange(4096)
    np.random.default_rng(cfg["train"]["shuffle_seed"]).shuffle(order)  # text_batches, epoch 0
    checked = order[: cfg["check"]["steps"] * cfg["train"]["train_batch_size"]].reshape(3, 2)
    assert traffic.labels(text, 4096)[checked].sum() == 0
    assert (lengths[checked].max(1) > cfg["sliding_window_size"]).all()
    assert 0.3 < (lengths > cfg["sliding_window_size"]).mean() < 0.37 and 3400 < lengths.mean() < 3650


def _last_row(capsys):
    sys.path.insert(0, str(BENCH))
    import run

    assert run.main(["--workload", CELL, "--seed", "11", "--seconds", "0.3", "--trace", "0",
                     "--benchmark-file", TINY]) == 0
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return row, {k for k, v in row["compared"].items() if not v["value"] <= v["limit"]}


@pytest.mark.parametrize("kind", ["window_dropped", "router_reads_m"])
def test_correct_is_false_with_the_fault_planted_in_the_program(kind, monkeypatch, capsys):
    sys.path.insert(0, str(BENCH / "tools"))
    import prove_frozen_smallthinker

    prove_frozen_smallthinker.plant(kind, monkeypatch.setattr)
    row, over = _last_row(capsys)
    assert row["correct"] is False and "hidden_gap" in over


def test_the_driver_refuses_checked_rows_that_all_fit_inside_the_window(monkeypatch, capsys):
    """A window as long as the block: no checked query reaches past it, and
    ``correct`` could not tell a window layer from a global one."""
    from harness import spec

    drivers = spec.load_module("drivers", "joint_trainer_frozen_smallthinker")
    real = drivers.model_config
    monkeypatch.setattr(drivers, "model_config", lambda cfg: real({**cfg, "sliding_window_size": 64}))
    sys.path.insert(0, str(BENCH))
    import run

    with pytest.raises(RuntimeError, match="no checked query reaches past the window"):
        run.main(["--workload", CELL, "--seed", "11", "--seconds", "0.3", "--trace", "0",
                  "--benchmark-file", TINY])


def test_flop_count_against_a_hand_count():
    from harness import spec

    cfg = json.loads((BENCH / "configs" / "smallthinker-21b-msivd.json").read_text())
    flops = spec.load_module("flops", cfg["flops"])
    attn = 2 * 2560 * 28 * 128 + 2 * 2560 * 4 * 128
    assert flops.attention_token_params(cfg) == attn and round(attn / 1e6, 2) == 20.97
    assert flops.layer_token_params(cfg) == attn + 2560 * 64
    assert flops.window_layers(cfg) == 3 * cfg["num_hidden_layers"] // 4
    assert flops.row_pairs(4096, 4096) == flops.row_pairs(4096, None) == 4096 * 4097 // 2
    assert flops.row_pairs(8192, 4096) == 4096 * 4097 // 2 + 4096 * 4096
    layers, n_win = cfg["num_hidden_layers"], flops.window_layers(cfg)
    c = {"steps": 1, "functions": 2, "tokens_real": 9000, "attn_pairs_global": 30_000_000,
         "attn_pairs_window": 20_000_000, "graph_nodes_real": 0, "graph_edges_real": 0,
         "moe_held_assignments": 54000 * layers}
    want = (2 * layers * (attn + 2560 * 64) * 9000             # projections and router, forward once
            + (2 * 28 * 2 * 128) * ((layers - n_win) * 30_000_000 + n_win * 20_000_000)
            + 2 * 3 * 2560 * 768 * 54000 * layers              # every real token's six experts
            + 6 * ((2560 + 256) * 2560 + 2560 * 2) * 2)        # the trained head, three passes
    assert flops.count(cfg, c) == want
    # the kernel's own counts: a full 8,192-token row of a global layer is 0.48 TFLOP and 134 MB
    full = flops.row_pairs(8192, None)
    assert flops.attention_ops(cfg, full) == 2 * 28 * 2 * 128 * 8192 * 8193 // 2
    assert round(flops.attention_ops(cfg, full) / 1e12, 2) == 0.48
    assert flops.attention_bytes(cfg, 8192) == 8192 * (2 * 28 + 2 * 4) * 128 * 2 == 134217728
    # a tiny configuration, every term by hand: 8 layers, 6 of them windowed (24)
    tiny = json.loads((BENCH / "configs" / "tiny-smallthinker-21b-msivd.json").read_text())
    t = {"steps": 1, "functions": 2, "tokens_real": 70, "attn_pairs_global": 40 * 41 // 2 + 465,
         "attn_pairs_window": flops.row_pairs(40, 24) + 465, "graph_nodes_real": 0,
         "graph_edges_real": 0, "moe_held_assignments": 70 * 3 * 8}
    assert flops.row_pairs(40, 24) == 300 + 16 * 24 and flops.row_pairs(30, 24) == 300 + 6 * 24
    t_attn = 2 * 64 * 4 * 16 + 2 * 64 * 2 * 16
    t_want = (2 * 8 * (t_attn + 64 * 8) * 70
              + (2 * 4 * 2 * 16) * (2 * (820 + 465) + 6 * (684 + 465))
              + 2 * 3 * 64 * 32 * 1680 + 6 * ((64 + 64) * 64 + 64 * 2) * 2)
    assert flops.count(tiny, t) == t_want
