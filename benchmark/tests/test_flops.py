"""Each FLOP count against a count made by hand."""

import json

import pytest
from conftest import BENCH
from harness import peaks, spec

flops = spec.load_module("flops", "roberta_fusion_train")


def test_encoder_matmul_params_of_codebert_base():
    cfg = json.loads((BENCH / "configs" / "linevul.json").read_text())
    # per layer: Q, K, V, O of 768 x 768 and two of 768 x 3072
    assert flops.encoder_matmul_params(cfg["model"]) == 12 * (4 * 589_824 + 2 * 2_359_296)
    assert flops.encoder_matmul_params(cfg["model"]) == 84_934_656


def test_linevul_step_by_hand():
    cfg = json.loads((BENCH / "configs" / "linevul.json").read_text())
    # one step, 16 rows of 512 real tokens each
    c = {"functions": 16, "tokens_real": 8192, "tokens_sq": 16 * 512 * 512}
    matmul = 6 * 84_934_656 * 8192
    attention = 3 * 12 * (2 * 2 * 512 * 512 * 768) * 16
    head = 6 * (768 * 768 + 768 * 2) * 16
    assert flops.count(cfg, c) == matmul + attention + head
    assert 4.6e12 < matmul + attention + head < 4.7e12  # ISSUE 25's 4.6 TFLOP a step


def test_frozen_ggnn_counts_its_forward_once():
    cfg = json.loads((BENCH / "configs" / "linevul-fusion.json").read_text())
    g = cfg["gnn"]
    # width 128: edge Linear 2*128*128, two GRU projections 2*128*384 each, per node and
    # round; one add of 128 per edge and round; gate 2*256 and weighted sum 2*256 per node
    per_node_round = 2 * 128 * 128 + 2 * (2 * 128 * 384)
    by_hand = 5 * (1000 * per_node_round + 2300 * 128) + 1000 * (2 * 256 + 2 * 256)
    assert flops.ggnn_forward_flops(g, 1000, 2300) == by_hand
    c = {"functions": 16, "tokens_real": 100, "tokens_sq": 1000,
         "graph_nodes_real": 1000, "graph_edges_real": 2300}
    with_gnn = flops.count(cfg, c)
    without = flops.count({**cfg, "use_gnn": False}, c)
    head_extra = 6 * (256 * 768) * 16
    assert with_gnn - without == by_hand + head_extra
    trained = flops.count({**cfg, "freeze_gnn": False}, c)
    assert trained - without == 3 * by_hand + head_extra


def test_peaks_table():
    assert peaks.of("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.of("TPU v9 imaginary")


def test_step_mfu_finds_the_count_by_the_configurations_name():
    from types import SimpleNamespace

    cfg = json.loads((BENCH / "configs" / "linevul.json").read_text())
    c = {"functions": 16, "tokens_real": 8192, "tokens_sq": 16 * 512 * 512}
    ctx = SimpleNamespace(config=cfg, counters=c, phases=SimpleNamespace(window_s=0.1),
                          device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    read = spec.load_module("readers", "step_mfu").read
    assert read(ctx, peak="bf16_flops_per_s") == pytest.approx(
        100.0 * flops.count(cfg, c) / 0.1 / 197e12)
    ctx.device["platform"] = "cpu"  # a device metric: nothing off the TPU
    assert read(ctx, peak="bf16_flops_per_s") is None
