"""The power-retention frozen-decoder configuration's tiny twin (beside
``test_smallthinker_frozen.py``): ``run.py`` end to end on the CPU with the
retention metrics, the appended cell and the new metrics through
``spec.load_cell`` by name, the shared traffic's checked batches, the
retention's operations and bytes and the step's FLOPs against hand counts, and
the roofline reader on a recorded slice. The model against the reference and
every planting are tier-1 cases (``tests/test_brumby.py``)."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest
from conftest import BENCH

ROOT = BENCH.parent
TINY = str(BENCH / "tests" / "BENCHMARK.brumby.tiny.json")
CELL = "tiny-brumby-14b-msivd.joint"
REAL = "brumby-14b-msivd.joint-8k"
ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "TF_CPP_MIN_LOG_LEVEL": "3",
       "JAX_COMPILATION_CACHE_DIR": ""}
NEW = {"retention_fused_share.train", "retention_chunk_fill.train",
       "retention_roofline_share.train"}
LAYER = ("power-retention decoder (llm/brumby.py, ops/power_retention.py, "
         "ops/power_retention_kernel.py)")


@pytest.mark.parametrize("trace", [0, 1])
def test_well_formed_last_line_with_the_retention_metrics(trace, tmp_path):
    env = {**ENV, "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"), "TMPDIR": str(tmp_path)}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", str(2**31 + 7),
         "--seconds", "1", "--trace", str(trace), "--benchmark-file", TINY],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    assert row["correct"] is True and row["failed"] == 0 and row["attempted"] > 0
    assert set(row["compared"]) == {"grad1_gap", "delta_gap", "hidden_mean_gap", "step_logit_gap",
                                    "step_count_gap"}
    if trace:  # no device metric off the TPU; the program's counters are exact anywhere
        m = row["metrics"]
        assert {"retention_fused_share.train", "retention_chunk_fill.train",
                "ggnn_fill.train", "pad_share_tokens.train"} <= set(m)
        assert "retention_roofline_share.train" not in m  # a device metric: the CPU has none
        assert not any(n.startswith(("moe_", "attn_", "ssm_", "latent_")) for n in m)
        assert m["retention_fused_share.train"]["value"] == 0  # the plain form runs here
        # the plain form visits every chunk: about the real share of the computed tokens
        # (the two read the steps of two different spans)
        assert m["retention_chunk_fill.train"]["value"] == pytest.approx(
            100 - m["pad_share_tokens.train"]["value"], abs=2.0)
    else:
        assert set(row["metrics"]) == {"train_functions_per_s", "setup_s"}


def test_the_appended_cell_and_the_new_metrics_resolve_by_name():
    from harness import spec

    bench = spec.load_benchmark()
    cell = spec.load_cell(REAL, bench)
    assert cell["cell"]["chips"] == 1 and bench["workloads"][-1]["name"] == REAL
    assert cell["cell"]["traffic"] == "precisebugs-text-graphs-8k-v151936"  # SmallThinker's own
    assert bench["configs"][-1]["name"] == cell["cell"]["config"] == "brumby-14b-msivd"
    cfg = cell["config"]
    assert (cfg["entry"], cfg["reference"], cfg["flops"]) == (
        "joint_trainer_frozen_brumby", "brumby_fusion", "brumby_fusion_train")
    names = {m["name"] for m in cell["per_layer"]}
    st = {m["name"] for m in spec.load_cell("smallthinker-21b-msivd.joint-8k", bench)["per_layer"]}
    # every cross-cell metric of a graph-carrying decoder cell, nothing of another decoder's
    assert names - st == NEW
    assert st - names == {n for n in st if n.startswith(("moe_", "attn_"))}
    assert [m["name"] for m in bench["per_layer"][-3:]] == sorted(NEW, key=[
        "retention_fused_share.train", "retention_chunk_fill.train",
        "retention_roofline_share.train"].index)
    by_name = {m["name"]: m for m in cell["per_layer"]}
    for name, num, den in (("retention_fused_share.train", "retention_fused", "retention_layers"),
                           ("retention_chunk_fill.train", "retention_tokens_real",
                            "retention_tokens_visited")):
        m = by_name[name]
        assert m["reader"] == "program_attr_quotient" and m["source"] == "program_counter"
        assert m["args"] == {"span": "loss.sync", "num": [num], "den": [den], "scale": 100.0}
    roof = by_name["retention_roofline_share.train"]
    assert (roof["reader"], roof["source"]) == ("trace_op_roofline_share", "device_trace")
    assert roof["args"] == {"op": "power_retention_fwd", "ops": "retention_ops",
                            "bytes": "retention_bytes"}
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == [REAL]
        assert (m["layer"], m["unit"], m["better"], m["moves"]) == (
            LAYER, "%", "higher", "train_functions_per_s")
    assert by_name["step_mfu.train"]["workloads"][-1] == REAL
    assert {m["name"] for m in cell["end_to_end"]} == {"train_functions_per_s", "setup_s"}
    assert set(cfg["limits"]) <= set(cfg["limit_reasons"])


def test_the_checked_batches_are_the_smallthinker_cells_all_negative_rows():
    from harness import spec, traffic

    bench = spec.load_benchmark()
    ours, st = (spec.load_cell(c, bench) for c in (REAL, "smallthinker-21b-msivd.joint-8k"))
    for key in ("train", "gnn", "head", "graph_join", "check"):
        if key == "check":
            assert {k: ours["config"][key][k] for k in ("steps", "warm_steps", "labels")} == {
                k: st["config"][key][k] for k in ("steps", "warm_steps", "labels")}
        else:
            assert ours["config"][key] == st["config"][key], key
    cfg = ours["config"]
    text = traffic.load_mix(traffic.load_mix(ours["cell"]["traffic"])["text"])
    assert text["vocab"] == cfg["vocab_size"] == 151936 and text["block"] == 8192
    lengths = traffic.sizes(text["length"], 4096, text["size_seed"])
    order = np.arange(4096)
    np.random.default_rng(cfg["train"]["shuffle_seed"]).shuffle(order)  # text_batches, epoch 0
    checked = order[: cfg["check"]["steps"] * cfg["train"]["train_batch_size"]].reshape(3, 2)
    assert traffic.labels(text, 4096)[checked].sum() == 0
    assert lengths[checked].tolist() == [[5903, 2428], [8192, 1786], [7503, 2490]]


def test_retention_ops_bytes_and_the_step_against_a_hand_count():
    from harness import spec

    cfg = json.loads((BENCH / "configs" / "brumby-14b-msivd.json").read_text())
    flops = spec.load_module("flops", cfg["flops"])
    layer = 2 * 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 8 + 3 * 5120 * 17408
    assert flops.layer_token_params(cfg) == layer and round(layer / 1e6, 2) == 330.34
    assert flops.feature_dim(128) == 8256
    c = {"steps": 1, "functions": 2, "tokens_real": 7064, "retention_tokens_visited": 7168,
         "graph_nodes_real": 0, "graph_edges_real": 0}
    # 2 D (d + 1) (40 + 8) = 102.2 MFLOP a real token a layer (ISSUE 41), 10 layers
    per_token = 2 * 8256 * 129 * 48
    assert round(per_token / 1e6, 1) == 102.2
    assert flops.retention_ops(cfg, c) == per_token * 10 * 7064
    assert round(flops.retention_ops(cfg, c) / 10 / 1e12, 2) == 0.72  # a layer: 3.67 ms at peak
    # q, k, v in and o out bfloat16, the gates float32: 24,608 bytes a token a layer
    assert flops.retention_bytes(cfg, c) == (80 + 16) * 128 * 2 * 10 * 7168 + 8 * 4 * 10 * 7168
    assert flops.retention_bytes(cfg, {**c, "retention_tokens_visited": 16384}) / 10 == 16384 * 24608
    want = 2 * 10 * layer * 7064 + per_token * 10 * 7064 + 6 * ((5120 + 256) * 5120 + 5120 * 2) * 2
    assert flops.count(cfg, c) == want
    tiny = json.loads((BENCH / "configs" / "tiny-brumby-14b-msivd.json").read_text())
    t = {**c, "tokens_real": 70, "retention_tokens_visited": 96}
    t_layer = 2 * 64 * 64 + 2 * 64 * 32 + 64 * 2 + 3 * 64 * 128
    t_ops = 2 * 136 * 17 * 6 * 2 * 70
    assert flops.retention_ops(tiny, t) == t_ops
    assert flops.retention_bytes(tiny, t) == ((8 + 4) * 16 * 2 + 2 * 4) * 2 * 96
    assert flops.count(tiny, t) == (2 * 2 * t_layer * 70 + t_ops
                                    + 6 * ((64 + 64) * 64 + 64 * 2) * 2)


def _ctx(device_ops, platform="tpu"):
    cfg = json.loads((BENCH / "configs" / "brumby-14b-msivd.json").read_text())
    return SimpleNamespace(
        config=cfg, counters={"tokens_real": 7064 * 20, "retention_tokens_visited": 7168 * 20},
        phases=SimpleNamespace(window_s=20.0, window_steps=20),
        device={"platform": platform, "kind": "TPU v5 lite", "count": 1},
        trace={"busy_s": 1.9, "window_s": 2.0, "device_ops": device_ops, "idle_gaps": []})


def test_the_roofline_reader_on_a_recorded_slice():
    from harness import spec

    reader = spec.load_module("readers", "trace_op_roofline_share")
    args = {"op": "power_retention_fwd", "ops": "retention_ops", "bytes": "retention_bytes"}
    # 2 s traced, 1.9 s of it busy, the kernel's two events 0.1 s: 0.1 / 1.9 of the window's 20 s
    ops = [["%power_retention_fwd.1 bf16[2,8192,5120]", 0.06], ["%fusion.3 bf16[2]", 0.9],
           ["%power_retention_fwd.2 bf16[2,8192,5120]", 0.04]]
    flops = spec.load_module("flops", "brumby_fusion_train")
    ctx = _ctx(ops)
    least = flops.retention_ops(ctx.config, ctx.counters) / 197e12  # bound by compute
    assert least > flops.retention_bytes(ctx.config, ctx.counters) / 819e9
    assert reader.read(ctx, **args) == pytest.approx(100 * least / (0.1 / 1.9 * 20))
    assert reader.read(_ctx(ops, platform="cpu"), **args) is None
    assert reader.read(_ctx([["%fusion.3 bf16[2]", 0.9]]), **args) is None  # no such kernel
