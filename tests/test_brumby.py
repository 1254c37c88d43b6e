"""The power-retention decoder (``llm/brumby.py``) against the plain reference
(``benchmark/reference/brumby_fusion.py``, the attention form: every pair's
weight whole, no state) on seeded weights at a tiny size: hidden states in
float32 and bfloat16 under left padding, the kernel's path against the plain
form's, the scanned stack against a loop over its layers, the ``FAMILIES`` row
and the presets, the published sizes, the ``retention`` counts on
``loss.sync`` through ``JointTrainer.train`` with the decoder frozen, the
reference's control and faults and every fault planted in the program reading
``correct: false``, the tiny preset through ``scripts/train_joint.py``."""

import json
import sys
import time
from pathlib import Path

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.traverse_util import unflatten_dict

from deepdfa_tpu.llm.brumby import (
    BrumbyConfig,
    BrumbyLayer,
    BrumbyModel,
    brumby_14b,
    gate_bias_init,
    tiny_brumby,
)

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "benchmark"
for p in (str(BENCH), str(BENCH / "tools"), str(ROOT / "scripts")):
    if p not in sys.path:
        sys.path.insert(0, p)
TINY_BENCH = BENCH / "tests" / "BENCHMARK.brumby.tiny.json"
CELL = "tiny-brumby-14b-msivd.joint"
COMPARED = ("grad1_gap", "delta_gap", "hidden_mean_gap", "step_logit_gap", "step_count_gap")
PLANTED = ("degree_1", "gate_dropped", "normaliser_dropped", "pads_in_state",
           "state_not_carried", "kv_head_mod", "qk_norm_skipped", "rope_dropped")
# the catalog row's ``config`` (model-configs/architectures.jsonl, Brumby-14B-Base)
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 5120,
    "intermediate_size": 17408, "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


@pytest.fixture(scope="module")
def bench():
    """The tiny twin of the benchmark's configuration: its file, the plain
    reference, the reference's weights for one seed, and the program's
    decoder built from them (stacked as the driver stacks them)."""
    from harness import spec, traffic

    cell = spec.load_cell(CELL, json.loads(TINY_BENCH.read_text()))
    cfg = cell["config"]
    reference = spec.load_module("reference", cfg["reference"])
    drivers = spec.load_module("drivers", cfg["entry"])
    w = reference.make_weights(cfg, 7)
    llm_cfg = drivers.model_config(cfg)
    stacked = drivers.Stacked(w, jnp.float32, reference.FLOAT32_LEAVES)
    params = unflatten_dict({n[4:]: stacked[n] for n in stacked if n.startswith("llm/")}, sep="/")
    data = traffic.generate(cell["cell"]["traffic"], 7, {"n_examples": 64})
    return dict(cell=cell, cfg=cfg, reference=reference, drivers=drivers, w=w,
                llm_cfg=llm_cfg, model=BrumbyModel(llm_cfg), params=params, data=data)


def _gap(got, want, mask):
    got, want = np.asarray(got, np.float64)[mask], np.asarray(want, np.float64)[mask]
    return (np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)).max()


def _rows(bench, pads):
    ids = np.asarray(bench["data"]["input_ids"][:len(pads)])
    mask = np.arange(ids.shape[1])[None] >= np.asarray(pads)[:, None]
    return jnp.asarray(ids), jnp.asarray(mask)


@pytest.mark.parametrize("pads", [(0, 0), (5, 16), (40, 63)])
def test_hidden_states_match_the_reference(bench, pads):
    ids, mask = _rows(bench, pads)
    want = bench["reference"].decoder(bench["cfg"], bench["w"], ids, mask)
    got = bench["model"].apply({"params": bench["params"]}, ids, mask)
    assert _gap(got, want, np.asarray(mask)) < 1e-5


def test_bfloat16_hidden_states_stay_near_the_float32_reference(bench):
    ids, mask = _rows(bench, (5, 30))
    cfg = tiny_brumby(dtype="bfloat16")
    cast = lambda path, x: x if path[-1].key == "g_bias" else x.astype(jnp.bfloat16)
    params = jax.tree_util.tree_map_with_path(cast, bench["params"])
    got = BrumbyModel(cfg).apply({"params": params}, ids, mask)
    want = bench["reference"].decoder(bench["cfg"], bench["w"], ids, mask)
    assert got.dtype == jnp.bfloat16 and _gap(got, want, np.asarray(mask)) < 5e-2


def _layer_by_layer(bench, ids, mask, method=None):
    """The stack as a loop over its layers, each a ``BrumbyLayer.apply``
    (``method`` in place of ``__call__``), then the final norm."""
    cfg, p = bench["llm_cfg"], bench["params"]
    positions = jnp.maximum(jnp.cumsum(mask.astype(jnp.int32), axis=-1) - 1, 0)
    x = p["embed_tokens"]["embedding"][ids]
    for i in range(cfg.num_hidden_layers):
        layer = jax.tree.map(lambda leaf: leaf[i], p["layers"])
        x, _ = BrumbyLayer(cfg).apply({"params": layer}, x, mask, positions, method=method)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + cfg.rms_norm_eps)
    return p["norm"]["weight"] * x


def test_the_scanned_stack_is_a_loop_over_its_layers(bench):
    ids, mask = _rows(bench, (0, 9))
    want = _layer_by_layer(bench, ids, mask)
    got = bench["model"].apply({"params": bench["params"]}, ids, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_the_kernels_path_is_the_plain_forms(monkeypatch):
    """At heads of 128 and whole chunks the model takes the kernel (patched to
    the interpreter): its states are the plain form's to bfloat16 rounding."""
    from deepdfa_tpu.ops import dispatch

    cfg = tiny_brumby(hidden_size=256, num_attention_heads=2, num_key_value_heads=1,
                      head_dim=128, retention_chunk=32)
    ids = jnp.asarray(np.random.default_rng(0).integers(3, 320, (2, 64)))
    mask = jnp.asarray(np.arange(64)[None] >= np.array([[0], [40]]))
    model = BrumbyModel(cfg)
    params = jax.jit(model.init)(jax.random.key(0), ids, mask)
    plain, s_plain = model.apply(params, ids, mask, mutable=["stats"])
    monkeypatch.setattr(dispatch, "device_mode", lambda: True)
    fused, s_fused = BrumbyModel(cfg).apply(params, ids, mask, mutable=["stats"])
    assert _gap(fused, plain, np.asarray(mask)) < 2e-2
    r_plain, r_fused = s_plain["stats"]["retention"], s_fused["stats"]["retention"]
    assert int(r_plain["fused"]) == 0 and int(r_fused["fused"]) == 2
    assert int(r_fused["chunks_computed"]) == 2 * (2 + 1) and int(r_plain["chunks_computed"]) == 2 * 4
    assert int(r_fused["chunks_needed"]) == int(r_plain["chunks_needed"]) == 2 * 3


@pytest.mark.parametrize("pads", [(0, 0), (5, 16), (40, 63), (64, 3)])
def test_the_chunked_halves_are_the_whole_blocks(bench, pads, monkeypatch):
    """Chunks of 16 positions at the block of 64: the real tokens' final
    states are the whole-block form's to float32 rounding (products of fewer
    than 64 rows take another CPU kernel; the worst token, a row's first,
    read 1.4e-6), and the reference's."""
    from deepdfa_tpu.llm import brumby

    monkeypatch.setattr(brumby, "DENSE_BLOCK", 16)
    ids, mask = _rows(bench, pads)
    real = np.asarray(mask)
    got = bench["model"].apply({"params": bench["params"]}, ids, mask)
    whole = _layer_by_layer(bench, ids, mask, lambda layer, *a: (layer.whole(*a), None))
    assert _gap(got, whole, real) < 5e-6
    want = bench["reference"].decoder(bench["cfg"], bench["w"], ids, mask)
    assert _gap(got, want, real) < 1e-5


def test_the_kernel_and_the_chunked_halves_skip_together(monkeypatch):
    """The kernel (interpreted) after halves in chunks of 32: a row's leading
    pad chunk is skipped by both, and its states are the plain form's over
    whole rows to bfloat16 rounding."""
    from deepdfa_tpu.llm import brumby
    from deepdfa_tpu.ops import dispatch

    cfg = tiny_brumby(hidden_size=256, num_attention_heads=2, num_key_value_heads=1,
                      head_dim=128, retention_chunk=32)
    ids = jnp.asarray(np.random.default_rng(0).integers(3, 320, (2, 64)))
    mask = jnp.asarray(np.arange(64)[None] >= np.array([[0], [40]]))
    model = BrumbyModel(cfg)
    params = jax.jit(model.init)(jax.random.key(0), ids, mask)
    plain, s_plain = model.apply(params, ids, mask, mutable=["stats"])
    monkeypatch.setattr(dispatch, "device_mode", lambda: True)
    monkeypatch.setattr(brumby, "DENSE_BLOCK", 32)
    fused, s_fused = BrumbyModel(cfg).apply(params, ids, mask, mutable=["stats"])
    assert _gap(fused, plain, np.asarray(mask)) < 2e-2
    r_plain, r_fused = s_plain["stats"]["retention"], s_fused["stats"]["retention"]
    assert int(r_fused["fused"]) == 2 and int(r_fused["chunks_computed"]) == 2 * (2 + 1)
    assert int(r_plain["tokens_dense"]) == 2 * 2 * 64  # whole rows: DENSE_BLOCK does not tile 64
    assert int(r_fused["tokens_dense"]) == 2 * (2 + 1) * 32


# the tree ``init`` draws for ``tiny_brumby`` at key 0, which the chunked halves leave as the
# whole-block layer draws it: shape and the sum of |leaf| (float64)
TINY_TREE = {
    "embed_tokens/embedding": ((320, 64), 325.0097708759616),
    "layers/input_norm/weight": ((2, 64), 128.0),
    "layers/mlp/down_proj/kernel": ((2, 128, 64), 1188.8028025953774),
    "layers/mlp/gate_proj/kernel": ((2, 64, 128), 1665.8254128245517),
    "layers/mlp/up_proj/kernel": ((2, 64, 128), 1685.2013822040576),
    "layers/post_attn_norm/weight": ((2, 64), 128.0),
    "layers/retention/g_bias": ((2, 2), 27.794021606445312),
    "layers/retention/g_proj/kernel": ((2, 64, 2), 27.056049299601),
    "layers/retention/k_norm/weight": ((2, 16), 32.0),
    "layers/retention/k_proj/kernel": ((2, 64, 32), 420.37668945161386),
    "layers/retention/o_proj/kernel": ((2, 64, 64), 843.0934264053758),
    "layers/retention/q_norm/weight": ((2, 16), 32.0),
    "layers/retention/q_proj/kernel": ((2, 64, 64), 845.4300169268863),
    "layers/retention/v_proj/kernel": ((2, 64, 32), 428.5556168733683),
    "norm/weight": ((64,), 64.0),
}


def test_init_draws_the_leaves_it_always_drew():
    from flax.traverse_util import flatten_dict

    ids = jnp.zeros((1, 32), jnp.int32)
    params = nn.meta.unbox(BrumbyModel(tiny_brumby()).init(jax.random.key(0), ids, ids == 0))
    leaves = flatten_dict(params["params"], sep="/")
    assert {n: x.shape for n, x in leaves.items()} == {n: s for n, (s, _) in TINY_TREE.items()}
    for n, (_, total) in TINY_TREE.items():
        assert np.abs(np.asarray(leaves[n], np.float64)).sum() == pytest.approx(total, rel=1e-6), n


def test_a_later_token_changes_no_earlier_state(bench):
    ids, mask = _rows(bench, (3,))
    base = bench["model"].apply({"params": bench["params"]}, ids, mask)
    moved = bench["model"].apply({"params": bench["params"]}, ids.at[0, 50].set(7), mask)
    np.testing.assert_array_equal(np.asarray(base)[0, :50], np.asarray(moved)[0, :50])
    assert not np.allclose(np.asarray(base)[0, 50:], np.asarray(moved)[0, 50:])


def test_the_stats_count_every_layer_and_the_chunks(bench):
    ids, mask = _rows(bench, (0, 20, 64))
    _, sown = bench["model"].apply({"params": bench["params"]}, ids, mask, mutable=["stats"])
    r = {k: int(v) for k, v in sown["stats"]["retention"].items()}
    layers = bench["llm_cfg"].num_hidden_layers
    assert r == {"layers": layers, "fused": 0, "chunks_needed": layers * (4 + 3 + 0),
                 "chunks_computed": layers * 12, "tokens_visited": layers * 12 * 16,
                 "tokens_real": layers * (64 + 44), "tokens_dense": layers * 2 * 64}


def test_the_dense_tokens_are_the_live_chunks_times_their_width(bench, monkeypatch):
    from deepdfa_tpu.llm import brumby

    monkeypatch.setattr(brumby, "DENSE_BLOCK", 16)
    ids, mask = _rows(bench, (0, 20, 64))
    _, sown = bench["model"].apply({"params": bench["params"]}, ids, mask, mutable=["stats"])
    layers = bench["llm_cfg"].num_hidden_layers
    assert int(sown["stats"]["retention"]["tokens_dense"]) == layers * (4 + 3 + 0) * 16


def test_seeded_gates_start_at_the_assumed_half_lives(bench):
    b = np.asarray(gate_bias_init(None, (8,)))
    lives = -1.0 / np.log2(1.0 / (1.0 + np.exp(-b)))
    np.testing.assert_allclose(lives, [64 * 2 ** i for i in range(8)], rtol=1e-3)
    np.testing.assert_allclose(
        np.asarray(bench["w"]["llm/layers_1/retention/g_bias"]),
        np.asarray(gate_bias_init(None, (2,))), rtol=1e-4)
    assert bench["params"]["layers"]["retention"]["g_bias"].shape == (2, 2)


def test_the_published_layer_counts_330_million_parameters_and_the_cell_8_16_gb():
    model = BrumbyModel(brumby_14b(num_hidden_layers=10))
    ids = jnp.zeros((1, 128), jnp.int32)
    shapes = nn.meta.unbox(jax.eval_shape(model.init, jax.random.key(0), ids, ids == 0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes["params"]))
    layer = (2 * 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 8 + 8 + 3 * 5120 * 17408
             + 2 * 128 + 2 * 5120)
    assert layer == 330_352_904
    assert n == 10 * layer + 151936 * 5120 + 5120
    assert 2 * n / 1e9 == pytest.approx(8.16, abs=0.01)


def test_the_published_config_reads_as_brumby_14b_and_other_layers_are_refused():
    assert BrumbyConfig.from_hf_dict(PUBLISHED) == brumby_14b()
    with pytest.raises(ValueError, match="another layer"):
        brumby_14b(hidden_act="gelu")
    with pytest.raises(ValueError, match="rope_scaling"):
        brumby_14b(rope_scaling={"type": "yarn"})
    with pytest.raises(ValueError, match="tile the block"):
        BrumbyModel(tiny_brumby()).init(jax.random.key(0), jnp.zeros((1, 40), jnp.int32))


# -- through JointTrainer.train, decoder frozen --------------------------------


@pytest.fixture(scope="module")
def followed(bench):
    """The benchmark's driver at the tiny size: ``JointTrainer.train`` with
    ``train_llm=False`` over the checked and warm steps, then the reference
    over the same rows."""
    from harness import compare, traffic
    from harness.phases import Phases

    driver = bench["drivers"].Driver(bench["cfg"], bench["reference"])
    data = traffic.generate(bench["cell"]["cell"]["traffic"], 11)
    driver.load(data, bench["reference"].make_weights(bench["cfg"], 11), 11)
    assert driver.jcfg.train_llm is False and isinstance(driver.trainer.llm, BrumbyModel)
    t0 = time.time()
    run = driver.run(Phases(t0, driver.setup_steps, 0.0))
    ran = (t0, time.time())  # the ring is the process's: other files' runs leave spans in it too
    ref = bench["reference"].run(bench["cfg"], data, 11, **run["follow"])
    nums = compare.numbers(bench["reference"].COMPARISON, run["readings"], ref)
    return dict(run=run, ref=ref, nums=nums, driver=driver, ran=ran)


@pytest.mark.parametrize("number", COMPARED)
def test_the_compared_numbers_are_under_their_tiny_limits(bench, followed, number):
    assert followed["nums"][number] <= bench["cfg"]["limits"][number]
    assert set(bench["cfg"]["limits"]) == set(COMPARED)
    delta = followed["run"]["readings"]["delta"]
    assert set(delta) == set(followed["ref"]["delta"]) and min(delta.values()) > 0
    assert all(n.startswith("fusion/") for n in delta)  # frozen: no decoder leaf is trained


def test_the_retention_counts_are_on_the_loss_sync_spans(followed):
    t0, t1 = followed["ran"]
    spans = [s for s in followed["driver"].trainer.telemetry.tracer.spans()
             if s.name == "loss.sync" and "retention_layers" in s.attrs and t0 <= s.start_s <= t1]
    assert len(spans) >= followed["driver"].setup_steps - 1  # the step in flight is not read
    for s in spans:
        r = {k[len("retention_"):]: v for k, v in s.attrs.items() if k.startswith("retention_")}
        assert set(r) == {"layers", "fused", "chunks_needed", "chunks_computed",
                          "tokens_visited", "tokens_real", "tokens_dense"}
        assert r["layers"] == 2 and r["fused"] == 0  # the CPU runs the plain form
        assert r["tokens_visited"] == 16 * r["chunks_computed"] >= r["tokens_real"] > 0
        assert r["chunks_needed"] <= r["chunks_computed"] == 2 * 4 * 4  # every chunk of 4 rows
        assert not any(k.startswith(("moe_", "attn_", "ssm_")) for k in s.attrs)
    tie = followed["run"]["readings"]["tie"]
    assert tie["counts"] == tie["step_counts"] and len(tie["counts"]) == 3


# -- the reference's control and faults, and faults planted in the program -------


@pytest.fixture(scope="module")
def tiny(bench):
    from harness import traffic

    cfg, reference = bench["cfg"], bench["reference"]
    data = traffic.generate(bench["cell"]["cell"]["traffic"], 5, {"n_examples": 64})
    follow = {"step_rows": [np.arange(4), np.arange(4, 8), np.arange(8, 12)], "total_steps": 100}
    return cfg, reference, data, follow, reference.run(cfg, data, 5, **follow)


@pytest.mark.parametrize("control", ["fp8", "half_batch", "state_unchanged", *PLANTED])
def test_reference_control_and_faults_read_incorrect(tiny, control):
    from harness import compare

    cfg, reference, data, follow, ref = tiny
    assert set(reference.FAULTS) | {"fp8"} >= {control}
    kw = {"precision": "fp8"} if control == "fp8" else {"fault": control}
    other = reference.run(cfg, data, 5, **follow, **kw)
    nums = compare.numbers(reference.COMPARISON, other, ref)
    limits = {k: v for k, v in cfg["limits"].items() if k in nums}  # one forward pass: no tie
    assert set(cfg["limits"]) - set(limits) == {"step_logit_gap", "step_count_gap"}
    assert not compare.judge(nums, limits)[0], nums


def _last_row(capsys):
    import run

    assert run.main(["--workload", CELL, "--seed", "11", "--seconds", "0.3", "--trace", "0",
                     "--benchmark-file", str(TINY_BENCH)]) == 0
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return row, {k for k, v in row["compared"].items() if not v["value"] <= v["limit"]}


@pytest.mark.parametrize("kind", PLANTED)
def test_correct_is_false_with_the_fault_planted_in_the_program(kind, monkeypatch, capsys):
    import prove_frozen_brumby

    prove_frozen_brumby.plant(kind, monkeypatch.setattr)
    row, over = _last_row(capsys)
    assert row["correct"] is False and "hidden_mean_gap" in over, row["compared"]
    assert not over & {"step_logit_gap", "step_count_gap"}  # the check's pass is planted alike


@pytest.mark.parametrize("kind,number", [("rope_dropped", "step_logit_gap"),
                                         ("count_off", "step_count_gap")])
def test_correct_is_false_with_a_fault_in_the_timed_step_alone(kind, number, monkeypatch, capsys):
    """The check's own pass stays good, so the number that reads it passes;
    what ties it to the timed step does not."""
    import prove_frozen_brumby
    from harness import spec

    drivers = spec.load_module("drivers", "joint_trainer_frozen_brumby")
    real_load = drivers.Driver.load

    def load(self, *a):
        real_load(self, *a)
        prove_frozen_brumby.step_alone(self, kind)

    monkeypatch.setattr(drivers.Driver, "load", load)
    row, over = _last_row(capsys)
    assert row["correct"] is False and number in over, row["compared"]
    assert "hidden_mean_gap" not in over, row["compared"]


# -- the normal path ----------------------------------------------------------


def test_tiny_preset_trains_through_train_joint(tmp_path, monkeypatch):
    monkeypatch.setenv("DEEPDFA_STORAGE", str(tmp_path / "storage"))
    import preprocess
    import train_joint

    preprocess.main(["--dataset", "demo", "--sample", "--workers", "1"])
    out = train_joint.main([
        "--preset", "tiny_brumby_msivd", "--dataset", "demo", "--sample", "--do_train",
        "--block_size", "32", "--output_dir", str(tmp_path / "run")])
    assert out["num_missing"] == 0
    epoch = [h for h in out["history"] if "train_loss" in h]
    assert len(epoch) == 1 and np.isfinite(epoch[0]["train_loss"])
    assert epoch[0]["telemetry"]["steps"] >= 2


def test_the_family_row_and_the_presets():
    from deepdfa_tpu.llm.families import FAMILIES, build_encoder
    from deepdfa_tpu.llm.presets import PRESETS

    real, small = PRESETS["brumby_14b_msivd"], PRESETS["tiny_brumby_msivd"]
    assert real.encoder_family == small.encoder_family == "brumby"
    assert real.llm == brumby_14b(num_hidden_layers=10)  # one stage of four; no width cut
    assert real.joint.block_size == 8192 and real.joint.train_batch_size == 2
    assert real.joint.learning_rate == 1e-6 and real.dataset == "precisebugs" and not real.finetuned
    assert real.joint.train_llm is False and real.joint.use_gnn and not real.joint.freeze_gnn
    assert small.llm == tiny_brumby(vocab_size=2048)
    fam = FAMILIES["brumby"]
    llm, params, _, cfg = build_encoder(fam, None, 16)
    assert isinstance(llm, BrumbyModel) and cfg == tiny_brumby(vocab_size=2048)
    assert fam.pool == "last" and fam.trained is False and fam.from_checkpoint is None
    assert params["layers"]["mlp"]["up_proj"]["kernel"].shape == (2, 64, 128)  # one scan body


def test_the_configuration_file_holds_the_catalog_entry_cut_in_depth_alone():
    d = json.loads((BENCH / "configs" / "brumby-14b-msivd.json").read_text())
    assert {k: d.get(k, "absent") for k in PUBLISHED if k != "num_hidden_layers"} == {
        k: v for k, v in PUBLISHED.items() if k != "num_hidden_layers"}
    assert d["num_hidden_layers"] == 10 and d["reduced"] == ["num_hidden_layers"]
    assert d["published"] == {"num_hidden_layers": 40}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == d["name"])
    assert entry["reduced"] == d["reduced"] and entry["source"] == d["source"]
    assert (d["entry"], d["reference"], d["flops"]) == (
        "joint_trainer_frozen_brumby", "brumby_fusion", "brumby_fusion_train")
    assert {"pipeline", "bytes", "assumed", "check", "limits", "limit_reasons"} <= set(d)
    assert set(d["limits"]) == set(COMPARED) <= set(d["limit_reasons"])
