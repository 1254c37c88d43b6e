"""The third frozen-decoder configuration's tiny twin (beside
``test_longcat_frozen.py`` and ``test_pangu_frozen.py``): ``run.py`` end to end
on the CPU with the new metric and the appended cell through
``spec.load_cell``; ``correct`` coming out false with each fault planted in the
program; and ``count`` / ``scan_ops`` / ``scan_bytes`` against hand counts. The
model against the reference, the padding cases and the reference's own control
and faults are tier-1 cases (``tests/test_jamba.py``, which also repeats the
plantings so that the driver's run holds them)."""

import json
import os
import subprocess
import sys

import pytest
from conftest import BENCH

ROOT = BENCH.parent
TINY = str(BENCH / "tests" / "BENCHMARK.jamba.tiny.json")
CELL = "tiny-jamba2-3b-msivd.joint"
REAL = "jamba2-3b-msivd.joint-2k"
ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "TF_CPP_MIN_LOG_LEVEL": "3",
       "JAX_COMPILATION_CACHE_DIR": ""}
COMPARED = {"grad1_gap", "delta_gap", "hidden_gap", "step_logit_gap", "step_count_gap"}
if str(BENCH / "tools") not in sys.path:
    sys.path.insert(0, str(BENCH / "tools"))


@pytest.mark.parametrize("trace", [0, 1])
def test_well_formed_last_line_with_the_new_metric(trace, tmp_path):
    env = {**ENV, "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"), "TMPDIR": str(tmp_path)}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", str(2**31 + 7),
         "--seconds", "1", "--trace", str(trace), "--benchmark-file", TINY],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    assert row["correct"] is True and row["failed"] == 0 and row["attempted"] > 0
    assert set(row["compared"]) == COMPARED
    if trace:  # no device metric off the TPU; the program's counters are exact anywhere
        assert row["metrics"]["ssm_fused_share.train"] == {"value": 0.0, "unit": "%"}  # no kernel yet
        assert not any(name.startswith("moe_") for name in row["metrics"])
        assert {"pad_share_tokens.train", "compiles.train", "inflight_read_share.train"} <= set(
            row["metrics"])
    else:
        assert set(row["metrics"]) == {"train_functions_per_s", "setup_s"}


def _planted_row(kind, monkeypatch, capsys, step_alone=False):
    import prove_frozen_jamba
    import run
    from harness import spec

    if step_alone:
        drivers = spec.load_module("drivers", "joint_trainer_frozen_jamba")
        real_load = drivers.Driver.load

        def load(self, *a):
            real_load(self, *a)
            prove_frozen_jamba.step_alone(self, kind)

        monkeypatch.setattr(drivers.Driver, "load", load)
    else:
        prove_frozen_jamba.plant(kind, monkeypatch.setattr)
    assert run.main(["--workload", CELL, "--seed", "11", "--seconds", "0.3", "--trace", "0",
                     "--benchmark-file", TINY]) == 0
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return row, {k for k, v in row["compared"].items() if not v["value"] <= v["limit"]}


@pytest.mark.parametrize("kind", [
    "mask_before_conv_skipped", "mask_after_conv_skipped", "state_bf16", "d_skip_skipped",
    "inner_norm_skipped", "attention_as_mamba", "rope_in_attention", "taps_reversed"])
def test_correct_is_false_with_the_fault_planted_in_the_program(kind, monkeypatch, capsys):
    row, over = _planted_row(kind, monkeypatch, capsys)
    assert row["correct"] is False and "hidden_gap" in over, row["compared"]


@pytest.mark.parametrize("kind,number", [("d_skip_skipped", "step_logit_gap"),
                                         ("count_off", "step_count_gap")])
def test_correct_is_false_with_a_fault_in_the_timed_step_alone(kind, number, monkeypatch, capsys):
    row, over = _planted_row(kind, monkeypatch, capsys, step_alone=True)
    assert row["correct"] is False and number in over and "hidden_gap" not in over, row["compared"]


def test_the_appended_cell_and_metric_resolve():
    from harness import spec, traffic

    bench = spec.load_benchmark()
    cell = spec.load_cell(REAL, bench)
    assert cell["cell"]["chips"] == 1
    assert cell["cell"]["traffic"] == "precisebugs-text-graphs-2k-v65536"
    cfg = cell["config"]
    assert (cfg["entry"], cfg["reference"], cfg["flops"]) == (
        "joint_trainer_frozen_jamba", "jamba_fusion", "jamba_fusion_train")
    names = {m["name"] for m in cell["per_layer"]}
    pangu = spec.load_cell("openpangu-ultra-msivd.joint-2k", bench)
    theirs = {m["name"] for m in pangu["per_layer"]}
    # the seventeen every decoder cell reports, and this cell's own; nothing of the routed layer
    assert names - theirs == {"ssm_fused_share.train"} and len(names) == 18
    assert {n for n in theirs - names} == {n for n in theirs if n.startswith(("moe_", "latent_"))}
    new = next(m for m in cell["per_layer"] if m["name"] == "ssm_fused_share.train")
    assert new["reader"] == "program_attr_quotient" and new["args"] == {
        "span": "loss.sync", "num": ["ssm_fused"], "den": ["ssm_layers"], "scale": 100.0}
    assert new["workloads"] == [REAL] and new["moves"] == "train_functions_per_s"
    assert new["layer"].startswith("state-space decoder (llm/jamba.py, ops/selective_scan.py")
    assert {m["name"] for m in cell["end_to_end"]} == {"train_functions_per_s", "setup_s"}
    assert set(cfg["limits"]) == COMPARED <= set(cfg["limit_reasons"])
    # the three decoder cells differ in the decoder only: same rows, lengths, labels, shuffle
    ours, lc = (traffic.load_mix(traffic.load_mix(c["cell"]["traffic"])["text"]) for c in (
        cell, spec.load_cell("longcat-flash-msivd.joint-2k", bench)))
    assert {k: v for k, v in ours.items() if k not in ("vocab", "assumed")} == {
        k: v for k, v in lc.items() if k not in ("vocab", "assumed")}
    assert ours["vocab"] == cfg["vocab_size"] == 65536  # the whole published vocabulary
    for key in ("train", "gnn", "head", "graph_join"):
        assert cfg[key] == pangu["config"][key], key


def test_flop_count_against_a_hand_count():
    from harness import spec

    cfg = json.loads((BENCH / "configs" / "jamba2-3b-msivd.json").read_text())
    flops = spec.load_module("flops", cfg["flops"])
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560  # the four matrices
    attn = 2 * 2560 * 2560 + 2 * 2560 * 128
    assert flops.mamba_token_params(cfg) == mamba == 41_123_840
    assert flops.attention_token_params(cfg) == attn == 13_762_560
    assert flops.attention_layers(cfg) == 2
    mlp = 3 * 2560 * 8192
    per_token = 26 * mamba + 2 * attn + 28 * mlp
    assert round(per_token / 1e9, 3) == 2.858  # of the 2.862B weights in layers: the matrices
    c = {"steps": 1, "functions": 4, "tokens_real": 4000, "tokens_sq": 4 * 1000 * 1000,
         "graph_nodes_real": 0, "graph_edges_real": 0}
    want = (2 * per_token * 4000                           # the frozen decoder, forward once
            + 2 * (2 * 20 * (128 + 128)) * 4_000_000 // 2  # causal scores and values, two layers
            + 6 * ((2560 + 256) * 2560 + 2560 * 2) * 4)    # the trained head, three passes
    assert flops.count(cfg, c) == want
    # a tiny configuration, every term by hand
    tiny = json.loads((BENCH / "configs" / "tiny-jamba2-3b-msivd.json").read_text())
    t_mamba = 64 * 256 + 128 * (8 + 16) + 8 * 128 + 128 * 64
    t_attn = 2 * 64 * 64 + 2 * 64 * 16
    t = {"steps": 1, "functions": 2, "tokens_real": 10, "tokens_sq": 52,
         "graph_nodes_real": 0, "graph_edges_real": 0}
    t_want = (2 * (6 * t_mamba + 2 * t_attn + 8 * 3 * 64 * 128) * 10
              + 2 * (2 * 4 * (16 + 16)) * 52 // 2 + 6 * ((64 + 64) * 64 + 64 * 2) * 2)
    assert flops.count(tiny, t) == t_want


def test_scan_ops_and_bytes_against_hand_counts():
    from harness import spec

    tiny = json.loads((BENCH / "configs" / "tiny-jamba2-3b-msivd.json").read_text())
    flops = spec.load_module("flops", tiny["flops"])
    # 10 tokens x 128 channels x (8 states x 7 + 8): exp, two products and an add into the
    # state, the product and add of the C reduction, delta * A; then x, D * c, the gate
    assert flops.scan_ops(tiny, 10) == 10 * 128 * (8 * 7 + 8) == 81_920
    # c, delta, z in and y out at [10, 128] bfloat16, B and C at [10, 8]; A and D float32
    assert flops.scan_bytes(tiny, 10) == 10 * (4 * 128 + 2 * 8) * 2 + (128 * 8 + 128) * 4 == 15_168
    real = json.loads((BENCH / "configs" / "jamba2-3b-msivd.json").read_text())
    tokens = 4 * 2048
    assert flops.scan_ops(real, tokens) == tokens * 5120 * 120  # 671M state updates x 7 + ...
    assert round(flops.scan_ops(real, tokens) / 1e9, 2) == 5.03
    assert round(flops.scan_bytes(real, tokens) / 1e6, 1) == 336.4  # 0.41 ms at 819 GB/s
