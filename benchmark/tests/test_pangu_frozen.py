"""The second frozen-decoder configuration's tiny twin (beside
``test_longcat_frozen.py``): ``run.py`` end to end on the CPU with the routing
metrics, the new metric and the appended cell through ``spec.load_cell``, and
the FLOP count against a hand count. The reference against its control and
faults, and ``correct`` coming out false with each fault planted in the
program, are tier-1 cases (``tests/test_pangu_moe.py``)."""

import json
import os
import subprocess
import sys

import pytest
from conftest import BENCH

ROOT = BENCH.parent
TINY = str(BENCH / "tests" / "BENCHMARK.pangu.tiny.json")
CELL = "tiny-openpangu-ultra-msivd.joint"
REAL = "openpangu-ultra-msivd.joint-2k"
ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "TF_CPP_MIN_LOG_LEVEL": "3",
       "JAX_COMPILATION_CACHE_DIR": ""}
ROUTED = {"moe_tokens_per_expert.train", "moe_load_max_over_mean.train", "moe_dropped.train",
          "moe_held_share.train", "latent_attn_fused_share.train"}


@pytest.mark.parametrize("trace", [0, 1])
def test_well_formed_last_line_with_the_routing_metrics(trace, tmp_path):
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
        assert ROUTED <= set(row["metrics"]) and "moe_zero_share.train" not in row["metrics"]
        assert row["metrics"]["moe_dropped.train"]["value"] == 0
        # 2 of 8 experts held: a quarter of the assignments under even routing
        assert 10 < row["metrics"]["moe_held_share.train"]["value"] < 45
        assert row["metrics"]["latent_attn_fused_share.train"]["value"] == 0  # no kernel off the TPU
    else:
        assert set(row["metrics"]) == {"train_functions_per_s", "setup_s"}


def test_the_appended_cell_and_metric_resolve():
    from harness import spec

    bench = spec.load_benchmark()
    cell = spec.load_cell(REAL, bench)
    assert cell["cell"]["chips"] == 1 and cell["cell"]["traffic"] == "precisebugs-text-graphs-2k-v19200"
    cfg = cell["config"]
    assert (cfg["entry"], cfg["reference"], cfg["flops"]) == (
        "joint_trainer_frozen_pangu", "pangu_moe_fusion", "pangu_moe_fusion_train")
    names = {m["name"] for m in cell["per_layer"]}
    theirs = {m["name"] for m in spec.load_cell("longcat-flash-msivd.joint-2k", bench)["per_layer"]}
    assert theirs - names == {"moe_zero_share.train"} and names <= theirs  # no zero experts here
    held = next(m for m in cell["per_layer"] if m["name"] == "moe_held_share.train")
    assert held["reader"] == "program_attr_quotient" and held["args"]["scale"] == 100.0
    assert held["workloads"] == ["longcat-flash-msivd.joint-2k", REAL]
    assert held["layer"] == next(m for m in cell["per_layer"]
                                 if m["name"] == "moe_dropped.train")["layer"]
    assert {m["name"] for m in cell["end_to_end"]} == {"train_functions_per_s", "setup_s"}
    assert set(cfg["limits"]) == set(cfg["limit_reasons"]) - {"expert_gap"}
    # the two decoder cells differ in the decoder only: same rows, lengths, labels, shuffle
    from harness import traffic

    ours, lc = (traffic.load_mix(traffic.load_mix(c["cell"]["traffic"])["text"]) for c in (
        cell, spec.load_cell("longcat-flash-msivd.joint-2k", bench)))
    assert {k: v for k, v in ours.items() if k not in ("vocab", "assumed")} == {
        k: v for k, v in lc.items() if k not in ("vocab", "assumed")}
    assert ours["vocab"] == cfg["vocab_size"] == 19200
    theirs_cfg = spec.load_cell("longcat-flash-msivd.joint-2k", bench)["config"]
    for key in ("train", "gnn", "head", "graph_join"):
        assert cfg[key] == theirs_cfg[key], key


def test_flop_count_against_a_hand_count():
    from harness import spec

    cfg = json.loads((BENCH / "configs" / "openpangu-ultra-msivd.json").read_text())
    flops = spec.load_module("flops", cfg["flops"])
    mla = 7680 * 1536 + 1536 * 128 * 192 + 7680 * 576 + 512 * 128 * 256 + 128 * 128 * 7680
    assert flops.mla_params(cfg) == mla and round(mla / 1e6, 1) == 196.6
    dense = mla + 3 * 7680 * 18432
    expert = mla + 7680 * 256 + 3 * 7680 * 2048
    assert flops.dense_layer_token_params(cfg) == dense and round(dense / 1e6, 1) == 621.2
    assert flops.expert_layer_token_params(cfg) == expert and round(expert / 1e6, 1) == 245.7
    c = {"steps": 1, "functions": 4, "tokens_real": 4000, "tokens_sq": 4 * 1000 * 1000,
         "graph_nodes_real": 0, "graph_edges_real": 0, "moe_held_assignments": 1000}
    want = (2 * (dense + 4 * expert) * 4000                    # one dense + four expert layers, forward once
            + 5 * (2 * 128 * (192 + 128)) * 4_000_000 // 2     # causal scores and values, one block a layer
            + 2 * 3 * 7680 * 2048 * 1000                       # the assignments to held experts
            + 6 * ((7680 + 256) * 7680 + 7680 * 2) * 4)        # the trained head, three passes
    assert flops.count(cfg, c) == want
    # a tiny configuration, every term by hand
    tiny = json.loads((BENCH / "configs" / "tiny-openpangu-ultra-msivd.json").read_text())
    t_mla = 64 * 32 + 32 * 4 * 24 + 64 * 24 + 16 * 4 * 32 + 64 * 64
    t = {"steps": 1, "functions": 2, "tokens_real": 10, "tokens_sq": 52,
         "graph_nodes_real": 0, "graph_edges_real": 0, "moe_held_assignments": 7}
    t_want = (2 * ((t_mla + 3 * 64 * 128) + 2 * (t_mla + 64 * 8 + 3 * 64 * 32)) * 10
              + 3 * (2 * 4 * (24 + 16)) * 52 // 2 + 2 * 3 * 64 * 32 * 7
              + 6 * ((64 + 64) * 64 + 64 * 2) * 2)
    assert flops.count(tiny, t) == t_want
