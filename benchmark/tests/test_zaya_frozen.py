"""The CCA decoder's configuration's tiny twin (beside
``test_smallthinker_frozen.py``): ``run.py`` end to end on the CPU with the
routing and CCA metrics, the appended cell and the new metric through
``spec.load_cell`` by name, every planting reading ``correct: false`` in the
program and in the timed step alone, and the FLOP count against a hand count.
The model against the reference, the parts of the layer and the halves of the
expert layer are tier-1 cases (``tests/test_zaya.py``)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest
from conftest import BENCH

ROOT = BENCH.parent
TINY = str(BENCH / "tests" / "BENCHMARK.zaya.tiny.json")
CELL = "tiny-zaya1-8b-msivd.joint"
REAL = "zaya1-8b-msivd.joint-8k"
ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "TF_CPP_MIN_LOG_LEVEL": "3",
       "JAX_COMPILATION_CACHE_DIR": ""}
ROUTED = {"moe_tokens_per_expert.train", "moe_load_max_over_mean.train", "moe_dropped.train",
          "moe_held_share.train", "moe_zero_share.train", "moe_gathered_share.train",
          "moe_gather_fill.train", "attn_needed_share.train"}
LAYER = "CCA decoder (llm/zaya.py, ops/cca.py, ops/gqa_attention.py)"
PLANTED = ("value_shift_dropped", "qk_mean_dropped", "depthwise_conv_dropped",
           "grouped_conv_dropped", "temperature_dropped", "rope_whole_head", "eda_dropped",
           "skip_never_taken", "expert_skipped")


@pytest.mark.parametrize("trace", [0, 1])
def test_well_formed_last_line_with_the_routing_and_cca_metrics(trace, tmp_path):
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
    assert row["observed"]["route_agree_share"] == 1.0
    if trace:  # no device metric off the TPU; the program's counters are exact anywhere
        m = row["metrics"]
        assert ROUTED | {"cca_fused_share.train", "ggnn_fill.train"} <= set(m)
        assert not {"moe_combine_fill.train", "latent_attn_fused_share.train"} & set(m)
        assert m["cca_fused_share.train"]["value"] == 0  # the CPU runs the blocked attention
        assert m["moe_dropped.train"]["value"] == 0
        assert m["moe_gathered_share.train"]["value"] == 100  # every expert is here
        assert 0 < m["moe_zero_share.train"]["value"] < 30  # the skip, taken at random routing
        assert abs(m["moe_held_share.train"]["value"] + m["moe_zero_share.train"]["value"]
                   - 100) < 1e-6
    else:
        assert set(row["metrics"]) == {"train_functions_per_s", "setup_s"}


def test_the_appended_cell_and_the_new_metric_resolve_by_name():
    from harness import spec, traffic

    bench = spec.load_benchmark()
    cell = spec.load_cell(REAL, bench)
    assert cell["cell"]["chips"] == 1
    assert cell["cell"]["traffic"] == "precisebugs-text-graphs-8k-v262272"
    cfg = cell["config"]
    assert (cfg["entry"], cfg["reference"], cfg["flops"]) == (
        "joint_trainer_frozen_zaya", "zaya_fusion", "zaya_fusion_train")
    names = {m["name"] for m in cell["per_layer"]}
    smallthinker = {m["name"] for m in spec.load_cell("smallthinker-21b-msivd.joint-8k",
                                                      bench)["per_layer"]}
    # the SmallThinker cell's metrics, the skip's share and the CCA's own
    assert names - smallthinker == {"moe_zero_share.train", "cca_fused_share.train"}
    assert smallthinker - names == set()
    by_name = {m["name"]: m for m in cell["per_layer"]}
    m = by_name["cca_fused_share.train"]
    assert m["reader"] == "program_attr_quotient" and m["workloads"] == [REAL]
    assert m["args"] == {"span": "loss.sync", "num": ["cca_fused"], "den": ["cca_layers"],
                         "scale": 100.0}
    assert (m["layer"], m["unit"], m["better"], m["moves"]) == (
        LAYER, "%", "higher", "train_functions_per_s")
    for name in ROUTED:
        assert REAL in by_name[name]["workloads"], name
    assert {m["name"] for m in cell["end_to_end"]} == {"train_functions_per_s", "setup_s"}
    assert set(cfg["limits"]) == set(cfg["limit_reasons"]) - {"expert_gap"}
    # the traffic is the SmallThinker cell's rows with ids over this vocabulary
    joined = traffic.load_mix(cell["cell"]["traffic"])
    text = traffic.load_mix(joined["text"])
    other = traffic.load_mix(traffic.load_mix("precisebugs-text-graphs-8k-v151936")["text"])
    assert (joined["generator"], joined["graphs"], joined["n_examples"]) == (
        "text_graphs", "bigvul-graphs", 4096)
    assert text["vocab"] == cfg["vocab_size"] == 262272 and text["block"] == 8192
    assert {k: v for k, v in text.items() if k not in ("vocab", "assumed")} == {
        k: v for k, v in other.items() if k not in ("vocab", "assumed")}
    # the three checked batches: no vulnerable function
    order = np.arange(4096)
    np.random.default_rng(cfg["train"]["shuffle_seed"]).shuffle(order)  # text_batches, epoch 0
    checked = order[: cfg["check"]["steps"] * cfg["train"]["train_batch_size"]]
    assert traffic.labels(text, 4096)[checked].sum() == 0
    lengths = traffic.sizes(text["length"], 4096, text["size_seed"])
    assert 3400 < lengths.mean() < 3650


def _last_row(capsys):
    sys.path.insert(0, str(BENCH))
    import run

    assert run.main(["--workload", CELL, "--seed", "11", "--seconds", "0.3", "--trace", "0",
                     "--benchmark-file", TINY]) == 0
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return row, {k for k, v in row["compared"].items() if not v["value"] <= v["limit"]}


@pytest.mark.parametrize("kind", PLANTED)
def test_correct_is_false_with_the_fault_planted_in_the_program(kind, monkeypatch, capsys):
    sys.path.insert(0, str(BENCH / "tools"))
    import prove_frozen_zaya

    prove_frozen_zaya.plant(kind, monkeypatch.setattr)
    row, over = _last_row(capsys)
    assert row["correct"] is False and "hidden_gap" in over


@pytest.mark.parametrize("kind", PLANTED)
def test_correct_is_false_with_the_fault_planted_in_the_timed_step_alone(
        kind, monkeypatch, capsys):
    """The check's own forward pass stays good, so the numbers that read it
    pass; what ties it to the timed step does not."""
    sys.path.insert(0, str(BENCH / "tools"))
    import prove_frozen_zaya
    from harness import spec

    drivers = spec.load_module("drivers", "joint_trainer_frozen_zaya")
    real_load = drivers.Driver.load

    def load(self, *a):
        real_load(self, *a)
        prove_frozen_zaya.prove_frozen.step_alone(self, kind)

    monkeypatch.setattr(drivers.Driver, "load", load)
    row, over = _last_row(capsys)
    assert row["correct"] is False
    assert over & {"step_logit_gap", "step_count_gap"}, row["compared"]
    assert not over & {"hidden_gap", "route_gap"}, row["compared"]


def test_flop_count_against_a_hand_count():
    from harness import spec

    cfg = json.loads((BENCH / "configs" / "zaya1-8b-msivd.json").read_text())
    flops = spec.load_module("flops", cfg["flops"])
    attn = 2 * 2048 * 8 * 128 + 2 * 2048 * 2 * 128
    conv = 2 * 10 * 128 * 128
    router = 2048 * 256 + 2 * 256 * 256 + 256 * 17
    assert flops.layer_token_params(cfg) == attn + conv + router == 6_230_272
    assert flops.pair_ops(cfg) == 4 * 128 * 8
    c = {"steps": 1, "functions": 2, "tokens_real": 9000, "attn_pairs_global": 30_000_000,
         "attn_tokens_visited": 9216, "graph_nodes_real": 0, "graph_edges_real": 0,
         "moe_held_assignments": 8000 * 20}
    want = (2 * 20 * (attn + conv + router) * 9000            # projections, convolution, router
            + 4 * 128 * 8 * 20 * 30_000_000                    # every causal pair, every layer
            + 2 * 3 * 2048 * 2048 * 8000 * 20                  # a real token's one expert
            + 6 * ((2048 + 256) * 2048 + 2048 * 2) * 2)        # the trained head, three passes
    assert flops.count(cfg, c) == want
    assert flops.cca_attention_ops(cfg, c) == 4 * 128 * 8 * 20 * 30_000_000
    # q and o at 8 heads, k and v at 2, bfloat16, at the kernel's visited positions, every layer
    assert flops.cca_attention_bytes(cfg, c) == 9216 * (2 * 8 + 2 * 2) * 128 * 2 * 20
    # a full 8,192-token row: 0.14 TFLOP and 42 MB a layer
    full = {"attn_pairs_global": 8192 * 8193 // 2, "attn_tokens_visited": 8192}
    assert round(flops.cca_attention_ops(cfg, full) / 20 / 1e12, 2) == 0.14
    assert flops.cca_attention_bytes(cfg, full) // 20 == 8192 * 20 * 128 * 2 == 41_943_040
