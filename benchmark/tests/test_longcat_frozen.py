"""The frozen-decoder configuration's tiny twin: ``run.py`` end to end on the
CPU, the plain reference against itself and against its own control and
faults, the FLOP count against a hand count, and ``correct`` coming out false
with each fault planted in the *program* — and with one planted in the timed
step alone, which only the numbers that tie the check's forward pass to that
step can see."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest
from conftest import BENCH

ROOT = BENCH.parent
TINY = str(BENCH / "tests" / "BENCHMARK.longcat.tiny.json")
CELL = "tiny-longcat-flash-msivd.joint"
ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "TF_CPP_MIN_LOG_LEVEL": "3",
       "JAX_COMPILATION_CACHE_DIR": ""}
NEW = {"moe_tokens_per_expert.train", "moe_load_max_over_mean.train", "moe_zero_share.train",
       "moe_dropped.train"}


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
        assert NEW <= set(row["metrics"])
        assert row["metrics"]["moe_dropped.train"]["value"] == 0
        assert 20 < row["metrics"]["moe_zero_share.train"]["value"] < 50  # 4 of 12 experts
        assert row["metrics"]["moe_load_max_over_mean.train"]["value"] >= 1
    else:
        assert set(row["metrics"]) == {"train_functions_per_s", "setup_s"}


@pytest.fixture(scope="module")
def tiny():
    from harness import spec, traffic

    cell = spec.load_cell(CELL, json.loads(open(TINY).read()))
    cfg = cell["config"]
    reference = spec.load_module("reference", cfg["reference"])
    data = traffic.generate(cell["cell"]["traffic"], 5, {"n_examples": 64})
    follow = {"step_rows": [np.arange(4), np.arange(4, 8), np.arange(8, 12)], "total_steps": 100}
    return cfg, reference, data, follow, reference.run(cfg, data, 5, **follow)


def test_reference_against_itself(tiny):
    from harness import compare

    cfg, reference, data, follow, ref = tiny
    again = reference.run(cfg, data, 5, **follow, routing=ref["routing"])
    nums = compare.numbers(reference.COMPARISON, again, ref)
    assert compare.judge(nums, {k: v for k, v in cfg["limits"].items() if k in nums})[0]
    assert nums["hidden_gap"] == 0 and nums["route_agree_share"] == 1.0
    assert nums["grad1_gap"] == 0 and nums["delta_gap"] == 0
    # weights belong to (seed, leaf name); decoder leaves are bfloat16 values
    w = reference.make_weights(cfg, 5)
    k = np.asarray(w["llm/layers_1/attn_0/q_b_proj/kernel"])
    assert np.array_equal(k, np.asarray(w["llm/layers_1/attn_0/q_b_proj/kernel"]))
    assert np.array_equal(k, k.astype("bfloat16").astype(np.float32)) and k.std() > 0
    assert not np.array_equal(k, np.asarray(reference.make_weights(cfg, 6)[
        "llm/layers_1/attn_0/q_b_proj/kernel"]))


@pytest.mark.parametrize("control", ["fp8", "half_batch", "state_unchanged", "no_shortcut",
                                     "zero_experts_return_0", "bias_ignored", "renormalised",
                                     "expert_skipped", "capacity_limit", "no_kv_scale",
                                     "no_rope_scores", "bias_ignored_sparse"])
def test_reference_control_and_faults_read_incorrect(tiny, control):
    from harness import compare

    cfg, reference, data, follow, ref = tiny
    assert set(reference.FAULTS) | {"fp8"} >= {control}
    kw = {"precision": "fp8"} if control == "fp8" else {"fault": control}
    other = reference.run(cfg, data, 5, **follow, **kw)
    good = reference.run(cfg, data, 5, **follow, routing=other["routing"])
    nums = compare.numbers(reference.COMPARISON, other, good)
    limits = {k: v for k, v in cfg["limits"].items() if k in nums}  # one forward pass: no tie
    assert set(cfg["limits"]) - set(limits) == {"step_logit_gap", "step_count_gap"}
    assert not compare.judge(nums, limits)[0], nums


def test_flop_count_against_a_hand_count():
    from harness import spec

    cfg = json.loads((BENCH / "configs" / "longcat-flash-msivd.json").read_text())
    flops = spec.load_module("flops", cfg["flops"])
    mla = 6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256 + 8192 * 6144
    assert flops.mla_params(cfg) == mla and round(mla / 1e6, 1) == 90.6
    per_token = 2 * mla + 2 * 3 * 6144 * 12288 + 6144 * 768
    assert flops.layer_token_params(cfg) == per_token and round(per_token / 1e6) == 639
    c = {"steps": 1, "functions": 4, "tokens_real": 4000, "tokens_sq": 4 * 1000 * 1000,
         "graph_nodes_real": 0, "graph_edges_real": 0, "moe_held_assignments": 1000}
    want = (2 * 4 * per_token * 4000                      # four layers, forward once
            + 4 * 2 * (2 * 64 * (192 + 128)) * 4_000_000 // 2   # causal scores and values
            + 2 * 3 * 6144 * 2048 * 1000                 # the assignments to held experts
            + 6 * ((6144 + 256) * 6144 + 6144 * 2) * 4)  # the trained head, three passes
    assert flops.count(cfg, c) == want
    # a full padded step, as the issue reckons it: 8192 tokens x 4 layers x ~1.47 GFLOP
    full = {**c, "tokens_real": 8192, "tokens_sq": 4 * 2048 * 2048, "moe_held_assignments": 2048}
    assert 40e12 < flops.count(cfg, full) < 50e12


def _planted(kind, monkeypatch):
    """Plant ``kind`` in the program, underneath the driver: the decoder's
    faults through ``tools/prove_frozen.plant``, the trained part's here."""
    import jax.numpy as jnp
    from deepdfa_tpu.llm import joint
    from harness import spec

    real_make = joint.make_joint_steps

    def steps(kind):
        def make(*a, **kw):
            train, evaluate = real_make(*a, **kw)

            def state_unchanged(state, llm, jb):
                _, loss, probs = train(state, llm, jb)
                return state, loss, probs

            def half_batch(state, llm, jb):
                keep = jnp.arange(jb.mask.shape[0]) < jb.mask.shape[0] // 2
                return train(state, llm, jb._replace(mask=jnp.asarray(jb.mask) & keep))

            return {"state_unchanged": state_unchanged, "half_batch": half_batch}[kind], evaluate
        return make

    if kind in ("state_unchanged", "half_batch"):
        monkeypatch.setattr(joint, "make_joint_steps", steps(kind))
    elif kind == "no_kv_scale":
        import dataclasses
        drivers = spec.load_module("drivers", "joint_trainer_frozen")
        real = drivers.model_config
        monkeypatch.setattr(drivers, "model_config",
                            lambda cfg: dataclasses.replace(real(cfg), mla_scale_kv_lora=False))
    else:
        _prove().plant(kind, monkeypatch.setattr)


def _prove():
    sys.path.insert(0, str(BENCH / "tools"))
    import prove_frozen
    return prove_frozen


def _last_row(capsys, planted_in_run=None):
    sys.path.insert(0, str(BENCH))
    import run

    assert run.main(["--workload", CELL, "--seed", "11", "--seconds", "0.3", "--trace", "0",
                     "--benchmark-file", TINY]) == 0
    out = capsys.readouterr()
    row = json.loads(out.out.strip().splitlines()[-1])
    over = {k for k, v in row["compared"].items() if not v["value"] <= v["limit"]}
    assert out.err.strip().splitlines()[-1] == f"correct: {row['correct']}"
    return row, over


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch", "no_shortcut",
                                  "zero_experts_return_0", "bias_ignored", "renormalised",
                                  "expert_skipped", "capacity_limit", "no_kv_scale",
                                  "no_rope_scores", "bias_ignored_sparse", "count_off"])
def test_correct_is_false_with_the_fault_planted_in_the_program(kind, monkeypatch, capsys):
    _planted(kind, monkeypatch)
    row, over = _last_row(capsys)
    assert row["correct"] is False and over
    if kind == "capacity_limit":  # the count says so too: the program cannot see the cap,
        assert row["compared"]["hidden_gap"]["value"] > 0.01  # the comparison does
    if kind == "bias_ignored_sparse":  # one token in 32: only the routing number sees it
        assert "route_gap" in over and 0 < row["compared"]["route_gap"]["value"] < 1 / 32
    if kind == "count_off":  # the check's forward pass counts wrongly too: the span's
        assert over == {"step_count_gap"}  # ``moe_dropped`` is the step's own


@pytest.mark.parametrize("kind", ["expert_skipped", "capacity_limit", "bias_ignored_sparse",
                                  "no_rope_scores", "count_off"])
def test_correct_is_false_with_the_fault_in_the_timed_step_alone(kind, monkeypatch, capsys):
    """The check's own forward pass stays good, so the numbers that read it
    pass; what ties it to the timed step does not."""
    from harness import spec

    drivers = spec.load_module("drivers", "joint_trainer_frozen")
    real_load = drivers.Driver.load

    def load(self, *a):
        real_load(self, *a)
        _prove().step_alone(self, kind)

    monkeypatch.setattr(drivers.Driver, "load", load)
    row, over = _last_row(capsys)
    assert row["correct"] is False
    # the forward pass is good; a gross fault also moves the step's own gradients
    assert over & {"step_logit_gap", "step_count_gap"}, row["compared"]
    assert not over & {"hidden_gap", "route_gap"}, row["compared"]
