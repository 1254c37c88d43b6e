"""The sandwich-norm latent-attention decoder with leading dense layers, a
shared expert and sigmoid-routed experts (``llm/pangu_moe.py``) against the
plain reference (``benchmark/reference/pangu_moe_fusion.py``) on seeded
weights at a tiny size: hidden states and routing, then the compared numbers
through ``JointTrainer.train`` with the decoder frozen; the shares of an
expert-parallel layer add up to the uncut layer; the router's cases; what a
leading layer lacks; every planted fault reads ``correct: false``; pad-mask
invariance; the tiny preset through ``scripts/train_joint.py``; and
``tiny_longcat``'s lowered step, which shares ``LatentAttention`` and the
held-experts path with this decoder, against the parent's digest."""

import dataclasses
import hashlib
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

from deepdfa_tpu.llm.pangu_moe import (
    ExpertLayer,
    PanguMoeConfig,
    PanguMoeModel,
    route,
    tiny_pangu_moe,
)
from deepdfa_tpu.ops.grouped import combine_blocks

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "benchmark"
for p in (str(BENCH), str(BENCH / "tools"), str(ROOT / "scripts")):
    if p not in sys.path:
        sys.path.insert(0, p)
TINY_BENCH = BENCH / "tests" / "BENCHMARK.pangu.tiny.json"
CELL = "tiny-openpangu-ultra-msivd.joint"
COMPARED = ("grad1_gap", "delta_gap", "hidden_gap", "route_gap", "step_logit_gap",
            "step_count_gap")


@pytest.fixture(scope="module")
def bench():
    """The tiny twin of the benchmark's configuration: its file, the plain
    reference, the reference's weights for one seed, and the program's
    decoder built from them."""
    from harness import spec, traffic

    cell = spec.load_cell(CELL, json.loads(TINY_BENCH.read_text()))
    cfg = cell["config"]
    reference = spec.load_module("reference", cfg["reference"])
    drivers = spec.load_module("drivers", cfg["entry"])
    w = reference.make_weights(cfg, 7)
    llm_cfg = drivers.model_config(cfg)
    params = unflatten_dict({n[4:]: w[n] for n in w if n.startswith("llm/")}, sep="/")
    data = traffic.generate(cell["cell"]["traffic"], 7, {"n_examples": 64})
    return dict(cell=cell, cfg=cfg, reference=reference, drivers=drivers, w=w,
                llm_cfg=llm_cfg, model=PanguMoeModel(llm_cfg), params=params, data=data)


def _u(t=24, d=64, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(t, d)), jnp.float32)


def _expert_params(cfg, seed=0):
    layer = ExpertLayer(cfg)
    return layer, nn.meta.unbox(layer.init(jax.random.key(seed), _u()[None], None)["params"])


# -- the program against the plain reference ---------------------------------


def test_hidden_states_and_routing_match_the_reference(bench):
    rows = np.arange(4)
    ids, mask = bench["data"]["input_ids"][rows], bench["data"]["pad_mask"][rows]
    assert not mask.all() and mask.any(1).all()  # left-padded rows, none empty
    hidden, sown = bench["model"].apply(
        {"params": bench["params"]}, ids, mask, mutable=["routing", "stats"])
    cfg = bench["llm_cfg"]
    chosen = np.stack([np.asarray(sown["routing"][f"layers_{i}"]["moe"]["choice"][0])
                       for i in range(cfg.first_k_dense_replace, cfg.num_hidden_layers)])
    ref_h, used, own, band = bench["reference"].decoder(
        bench["cfg"], bench["w"], ids, mask, routing=chosen)
    gap = np.linalg.norm(np.asarray(hidden - ref_h)[mask], axis=-1) / np.linalg.norm(
        np.asarray(ref_h)[mask], axis=-1)
    assert gap.max() < 1e-4
    assert np.asarray(band).max() < bench["cfg"]["check"]["route_epsilon"]
    agree = (np.sort(chosen, -1) == np.sort(np.asarray(own), -1)).all(-1)
    assert agree[:, mask].mean() > 0.99 and (np.asarray(band)[agree] == 0).all()
    assert (chosen[:, ~mask] == -1).all()  # a pad token is routed nowhere
    counts = jax.device_get(sown["stats"]["moe"])
    assert counts["dropped"] == 0 and counts["held"] > 0 and counts["zero"] == 0
    assert counts["held"] + counts["absent"] == counts["assigned"]
    assert counts["layers"] == 2 and counts["slots"] == 2 * 2  # expert layers only
    assert counts["assigned"] == mask.sum() * cfg.num_experts_per_tok * 2
    attn = jax.device_get(sown["stats"]["attn"])
    assert attn["layers"] == cfg.num_hidden_layers and attn["fused"] == 0  # one block a layer


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
    assert driver.jcfg.train_llm is False and isinstance(driver.trainer.llm, PanguMoeModel)
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
    assert followed["nums"]["route_agree_share"] > 0.99
    delta = followed["run"]["readings"]["delta"]
    assert set(delta) == set(followed["ref"]["delta"]) and min(delta.values()) > 0


@pytest.mark.parametrize("number,limit", [
    ("loss1_gap", 1e-5), ("loss3_gap", 1e-5), ("grad1_gap", 1e-4), ("delta_gap", 1e-3),
    ("hidden_gap", 1e-4), ("pooled_gap", 1e-4), ("logit_gap", 1e-4),
])
def test_train_steps_match_the_reference_closely(followed, number, limit):
    assert followed["nums"][number] <= limit


def test_routing_counts_are_on_the_loss_sync_spans(followed):
    t0, t1 = followed["ran"]
    spans = [s for s in followed["driver"].trainer.telemetry.tracer.spans()
             if s.name == "loss.sync" and "moe_held" in s.attrs and t0 <= s.start_s <= t1]
    assert len(spans) >= followed["driver"].setup_steps - 1  # the step in flight is not read
    _, width = combine_blocks(0, followed["driver"].trainer.llm.cfg.moe_chunk_rows)
    for s in spans:
        a = s.attrs
        assert a["moe_dropped"] == 0 and a["moe_zero"] == 0 and a["moe_layers"] == 2
        assert a["moe_held"] + a["moe_absent"] == a["moe_assigned"] > 0
        # the combine's blocks: only the last one of a layer's loop is not full
        assert a["moe_held"] <= a["moe_combined"] < a["moe_held"] + width * a["moe_layers"]
        assert a["attn_layers"] == 3 and a["attn_fused"] == 0
    # the checked steps' routing has one entry an expert layer
    assert followed["run"]["readings"]["routing"][0].shape[0] == 2


# -- the shares add up --------------------------------------------------------


def test_shares_of_all_ranks_add_up_to_the_uncut_layer(bench):
    """The held-experts part of every rank (2 of 8 routed experts each)
    summed, the shared expert counted once, equals the uncut expert layer —
    and the plain reference's with all experts held."""
    whole = tiny_pangu_moe()
    layer, p = _expert_params(whole)
    u = _u(40)[None]
    mask = jnp.asarray(np.arange(40) >= 5)[None]
    full, counts = layer.apply({"params": p}, u, mask)
    none_held = {k: (v[:1] * 0 if k.startswith("experts_") else v) for k, v in p.items()}
    far = dataclasses.replace(whole, n_routed_experts=9, experts_held=(8, 9))
    router9 = jnp.concatenate([p["router_kernel"], jnp.full((64, 1), -1e3)], 1)  # never chosen
    shared, _ = ExpertLayer(far).apply(
        {"params": {**none_held, "router_kernel": router9}}, u, mask)
    total, held = shared[0], 0
    for r in range(4):
        cfg_r = dataclasses.replace(whole, experts_held=(2 * r, 2 * r + 2))
        p_r = {k: (v[2 * r:2 * r + 2] if k.startswith("experts_") else v) for k, v in p.items()}
        out_r, c_r = ExpertLayer(cfg_r).apply({"params": p_r}, u, mask)
        total = total + (out_r[0] - shared[0])
        held += int(c_r["held"])
        assert int(c_r["dropped"]) == 0 and int(c_r["zero"]) == 0
        assert int(c_r["held"]) + int(c_r["absent"]) == int(counts["assigned"])
    np.testing.assert_allclose(total, full[0], atol=2e-5)
    assert held == int(counts["held"]) == int(counts["assigned"]) and int(counts["absent"]) == 0
    ref = bench["reference"]
    m = {"num_experts_per_tok": 3, "lo": 0, "n_held": 8, "routed_scaling_factor": 2.5}
    w = {k: p[k] for k in ("router_kernel", "experts_gate", "experts_up", "experts_down")}
    w.update({f"shared_expert/{k}/kernel": v["kernel"] for k, v in p["shared_expert"].items()})
    plain, *_ = ref._moe(m, lambda a: a, None, 0.0, w, u[0], mask[0], None)
    np.testing.assert_allclose(plain, full[0], atol=2e-5)


def test_dense_layer_and_attention_are_whole_on_every_rank(bench):
    """What every chip computes alike is counted once: a leading layer, and an
    expert layer's attention, have no parameter that depends on the range held."""
    shapes = lambda held: jax.tree.map(lambda x: x.shape, jax.eval_shape(
        lambda: PanguMoeModel(tiny_pangu_moe(experts_held=held)).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32), jnp.ones((1, 8), bool)))["params"])
    a, b = nn.meta.unbox(shapes((0, 2))), nn.meta.unbox(shapes((0, 8)))
    assert a["layers_0"] == b["layers_0"] and a["layers_1"]["attn"] == b["layers_1"]["attn"]
    assert a["layers_1"]["moe"]["shared_expert"] == b["layers_1"]["moe"]["shared_expert"]
    assert a["layers_1"]["moe"]["router_kernel"] == b["layers_1"]["moe"]["router_kernel"] == (64, 8)
    assert a["layers_1"]["moe"]["experts_up"] == (2, 64, 32)
    assert b["layers_1"]["moe"]["experts_up"] == (8, 64, 32)


# -- the router ---------------------------------------------------------------


@pytest.mark.parametrize("held", [None, (0, 2), (6, 8)])
def test_gates_are_renormalised_over_all_chosen_whatever_is_held(held):
    cfg = tiny_pangu_moe(experts_held=held)
    _, p = _expert_params(tiny_pangu_moe())
    x = _u(64)
    choice, gates = route(x, p["router_kernel"], cfg)
    assert choice.shape == gates.shape == (64, cfg.num_experts_per_tok)
    np.testing.assert_allclose(gates.sum(-1), cfg.routed_scaling_factor, rtol=1e-6)
    s = jax.nn.sigmoid(x @ p["router_kernel"])
    top = np.sort(np.asarray(s), -1)[:, ::-1][:, :3]
    np.testing.assert_allclose(np.sort(np.asarray(gates), -1)[:, ::-1],
                               2.5 * top / top.sum(-1, keepdims=True), rtol=1e-5)
    # the same choice and gates on every rank: what is held enters after the router
    c0, g0 = route(x, p["router_kernel"], tiny_pangu_moe())
    assert np.array_equal(choice, c0) and np.array_equal(gates, g0)


def test_gates_without_renormalisation_are_the_scaled_scores():
    cfg = tiny_pangu_moe(norm_topk_prob=False)
    _, p = _expert_params(tiny_pangu_moe())
    x = _u(32)
    choice, gates = route(x, p["router_kernel"], cfg)
    s = jax.nn.sigmoid(x @ p["router_kernel"])
    np.testing.assert_allclose(gates, 2.5 * jnp.take_along_axis(s, choice, -1), rtol=1e-5)


def test_a_leading_layer_has_no_router_and_sows_no_choice(bench):
    params, cfg = bench["params"], bench["llm_cfg"]
    assert cfg.first_k_dense_replace == 1
    assert set(params["layers_0"]) == {"attn", "ffn", "input_norm", "post_attn_norm",
                                       "pre_mlp_norm", "post_mlp_norm"}
    assert set(params["layers_1"]) == {"attn", "moe", "input_norm", "post_attn_norm",
                                       "pre_mlp_norm", "post_mlp_norm"}
    assert set(params["layers_1"]["moe"]) == {"router_kernel", "shared_expert", "experts_gate",
                                              "experts_up", "experts_down"}
    ids, mask = bench["data"]["input_ids"][:2], bench["data"]["pad_mask"][:2]
    _, sown = bench["model"].apply({"params": params}, ids, mask, mutable=["routing", "stats"])
    assert set(sown["routing"]) == {"layers_1", "layers_2"}
    # all layers dense: no router anywhere, so no ``moe`` counts at all
    dense = tiny_pangu_moe(first_k_dense_replace=3)
    model = PanguMoeModel(dense)
    p = model.init(jax.random.key(0), ids, mask)["params"]
    _, sown = model.apply({"params": p}, ids, mask, mutable=["routing", "stats"])
    assert "routing" not in sown and set(sown["stats"]) == {"attn"}


def test_config_reads_the_published_keys_and_refuses_what_it_cannot_build():
    d = json.loads((BENCH / "configs" / "openpangu-ultra-msivd.json").read_text())
    drivers_cfg = PanguMoeConfig.from_hf_dict({**d, "n_routed_experts": 256})
    assert (drivers_cfg.hidden_size, drivers_cfg.num_attention_heads, drivers_cfg.q_lora_rank,
            drivers_cfg.kv_lora_rank) == (7680, 128, 1536, 512)
    assert (drivers_cfg.num_hidden_layers, drivers_cfg.first_k_dense_replace) == (5, 1)
    assert drivers_cfg.held == (0, 16) and drivers_cfg.num_experts_per_tok == 8
    assert drivers_cfg.rope_theta == 25_600_000 and drivers_cfg.routed_scaling_factor == 2.5
    assert PanguMoeConfig().held == (0, 256) and PanguMoeConfig().num_hidden_layers == 61
    with pytest.raises(ValueError, match="no range"):
        PanguMoeConfig(experts_held=(250, 260))
    with pytest.raises(ValueError, match="no count"):
        PanguMoeConfig(num_hidden_layers=2, first_k_dense_replace=3)
    with pytest.raises(ValueError, match="sandwich_norm"):
        PanguMoeConfig(sandwich_norm=False)
    from deepdfa_tpu.llm.presets import PRESETS

    real = PRESETS["openpangu_ultra_msivd"]
    assert real.encoder_family == "pangu_moe" and real.llm == dataclasses.replace(
        drivers_cfg, max_position_embeddings=real.llm.max_position_embeddings)
    assert real.joint.block_size == 2048 and real.joint.train_batch_size == 4
    assert real.joint.train_llm is False and real.joint.use_gnn and not real.joint.freeze_gnn


def test_the_configuration_file_holds_the_catalog_entry_but_for_reduced():
    d = json.loads((BENCH / "configs" / "openpangu-ultra-msivd.json").read_text())
    published = {
        "attention_bias": False, "first_k_dense_replace": 3, "hidden_act": "silu",
        "hidden_size": 7680, "intermediate_size": 18432, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
        "moe_intermediate_size": 2048, "n_routed_experts": 256, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 61, "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_theta": 25600000, "routed_scaling_factor": 2.5,
        "sandwich_norm": True, "tie_word_embeddings": False, "v_head_dim": 128,
        "vocab_size": 153600}
    assert {k for k, v in published.items() if d.get(k) != v} == set(d["reduced"])
    assert d["published"] == {k: published[k] for k in d["reduced"]}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == d["name"])
    assert entry["reduced"] == d["reduced"] and entry["source"] == d["source"]
    # frozen bytes as the file states them: 9.56 GB bfloat16
    mla = 7680 * 1536 + 1536 * 128 * 192 + 7680 * 576 + 512 * 128 * 256 + 128 * 128 * 7680
    expert = 3 * 7680 * 2048
    frozen = 2 * ((mla + 3 * 7680 * 18432) + 4 * (mla + expert + 16 * expert)
                  + 19200 * 7680) + 4 * (4 * 256 * 7680)
    assert round(mla / 1e6, 1) == 196.6 and round(frozen / 1e9, 2) == 9.56


def test_weights_carry_their_logical_axes():
    from deepdfa_tpu.llm.llama import LOGICAL_RULES

    cfg = tiny_pangu_moe(experts_held=(2, 4))
    abstract = jax.eval_shape(lambda: PanguMoeModel(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), jnp.ones((1, 8), bool)))
    specs = nn.get_partition_spec(abstract)["params"]["layers_1"]
    assert specs["moe"]["experts_gate"][0] == "experts"
    mesh_axes = nn.logical_to_mesh(specs, LOGICAL_RULES)
    assert mesh_axes["moe"]["experts_down"][0] is None  # no exchange yet: no mesh axis
    # the shared expert is a dense FFN to the mesh: ("embed", "mlp"), no axis of its own
    P = jax.sharding.PartitionSpec
    assert mesh_axes["moe"]["shared_expert"]["gate_proj"]["kernel"] == P("fsdp", "tp")
    assert mesh_axes["moe"]["shared_expert"]["down_proj"]["kernel"] == P("tp", "fsdp")
    assert mesh_axes["attn"]["q_b_proj"]["kernel"] == P(None, "tp")
    assert {name for name, _ in LOGICAL_RULES} >= {"experts", "expert_mlp", "latent", "router"}


# -- planted faults -----------------------------------------------------------


@pytest.fixture(scope="module")
def tiny(bench):
    from harness import traffic

    cfg, reference = bench["cfg"], bench["reference"]
    data = traffic.generate(bench["cell"]["cell"]["traffic"], 5, {"n_examples": 64})
    follow = {"step_rows": [np.arange(4), np.arange(4, 8), np.arange(8, 12)], "total_steps": 100}
    return cfg, reference, data, follow, reference.run(cfg, data, 5, **follow)


def test_reference_against_itself(tiny):
    from harness import compare

    cfg, reference, data, follow, ref = tiny
    again = reference.run(cfg, data, 5, **follow, routing=ref["routing"])
    nums = compare.numbers(reference.COMPARISON, again, ref)
    assert nums["hidden_gap"] == 0 and nums["route_agree_share"] == 1.0
    assert nums["grad1_gap"] == 0 and nums["delta_gap"] == 0
    assert ref["routing"][0].shape[0] == 2  # one entry an expert layer
    w = reference.make_weights(cfg, 5)
    k = np.asarray(w["llm/layers_1/moe/shared_expert/up_proj/kernel"])
    assert np.array_equal(k, k.astype("bfloat16").astype(np.float32)) and k.std() > 0
    assert "llm/layers_0/moe/router_kernel" not in w and "llm/layers_0/ffn/up_proj/kernel" in w


@pytest.mark.parametrize("control", ["fp8", "half_batch", "state_unchanged", "shared_skipped",
                                     "not_renormalised", "scaling_one", "softmax_scores",
                                     "post_norm_skipped", "dense_as_experts", "expert_skipped"])
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


def _last_row(capsys):
    sys.path.insert(0, str(BENCH))
    import run

    assert run.main(["--workload", CELL, "--seed", "11", "--seconds", "0.3", "--trace", "0",
                     "--benchmark-file", str(TINY_BENCH)]) == 0
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return row, {k for k, v in row["compared"].items() if not v["value"] <= v["limit"]}


@pytest.mark.parametrize("kind", ["shared_skipped", "not_renormalised", "scaling_one",
                                  "softmax_scores", "post_norm_skipped", "dense_as_experts",
                                  "expert_skipped", "count_off"])
def test_correct_is_false_with_the_fault_planted_in_the_program(kind, monkeypatch, capsys):
    import prove_frozen_pangu

    prove_frozen_pangu.plant(kind, monkeypatch.setattr)
    row, over = _last_row(capsys)
    assert row["correct"] is False and over
    if kind == "count_off":  # the check's own pass counts wrongly too: only the span differs
        assert over == {"step_count_gap"}
    elif kind == "dense_as_experts":  # a fifth router's counts on the span, four in the check
        assert {"hidden_gap", "step_count_gap"} <= over
    else:
        assert "hidden_gap" in over


def test_correct_is_false_with_an_expert_skipped_in_the_timed_step_alone(monkeypatch, capsys):
    """The check's own forward pass stays good, so the numbers that read it
    pass; what ties it to the timed step does not."""
    import prove_frozen_pangu
    from harness import spec

    drivers = spec.load_module("drivers", "joint_trainer_frozen_pangu")
    real_load = drivers.Driver.load

    def load(self, *a):
        real_load(self, *a)
        prove_frozen_pangu.prove_frozen.step_alone(self, "expert_skipped")

    monkeypatch.setattr(drivers.Driver, "load", load)
    row, over = _last_row(capsys)
    assert row["correct"] is False
    assert over & {"step_logit_gap", "step_count_gap"}, row["compared"]
    assert not over & {"hidden_gap", "route_gap"}, row["compared"]


# -- padding ------------------------------------------------------------------


def test_pooled_state_is_invariant_under_left_padding(bench):
    model, params = bench["model"], bench["params"]
    body = np.random.default_rng(5).integers(3, 320, size=20).astype(np.int32)

    def pooled(pad, fill):
        ids = np.concatenate([np.full(pad, fill, np.int32), body])[None]
        mask = (np.arange(pad + 20) >= pad)[None]
        return np.asarray(model.apply({"params": params}, ids, mask))[0, -1]

    base = pooled(0, 1)
    for pad, fill in [(12, 1), (44, 1), (44, 77)]:
        np.testing.assert_allclose(pooled(pad, fill), base, atol=2e-4)


# -- the normal path ----------------------------------------------------------


def test_tiny_preset_trains_through_train_joint(tmp_path, monkeypatch):
    monkeypatch.setenv("DEEPDFA_STORAGE", str(tmp_path / "storage"))
    import preprocess
    import train_joint

    preprocess.main(["--dataset", "demo", "--sample", "--workers", "1"])
    out = train_joint.main([
        "--preset", "tiny_pangu_moe_msivd", "--dataset", "demo", "--sample", "--do_train",
        "--block_size", "32", "--output_dir", str(tmp_path / "run")])
    assert out["num_missing"] == 0
    epoch = [h for h in out["history"] if "train_loss" in h]
    assert len(epoch) == 1 and np.isfinite(epoch[0]["train_loss"])
    assert epoch[0]["telemetry"]["steps"] >= 2
    with pytest.raises(SystemExit, match="contradicts preset"):
        train_joint.main(["--preset", "tiny_pangu_moe_msivd", "--encoder", "longcat"])


def test_encoder_flag_alone_builds_the_hermetic_decoder():
    from deepdfa_tpu.llm.families import FAMILIES, build_encoder

    fam = FAMILIES["pangu_moe"]
    llm, params, _, cfg = build_encoder(fam, None, 16)
    assert isinstance(llm, PanguMoeModel) and cfg == tiny_pangu_moe(vocab_size=2048)
    assert fam.pool == "last" and fam.trained is False and fam.from_checkpoint is None
    assert not any(isinstance(x, nn.Partitioned) for x in jax.tree.leaves(
        params, is_leaf=lambda x: isinstance(x, nn.Partitioned)))


# -- what the other sparse decoder keeps ---------------------------------------

# sha256 of ``jit(train_step).lower(...).as_text()`` of ``tiny_longcat`` behind the frozen
# joint step (jax 0.9.0), made by this very function: ``LatentAttention``, the rope and the
# held-experts path serve two decoders, so a change to ``longcat.py`` or ``ops/grouped.py``
# that is meant for one of them must keep LongCat's program byte for byte. A PR that means
# to change it replaces this.
LONGCAT_STEP = "e4f8555bd8577c19ff42123803abe6da0aa60db0aca1197588fb7a67754f5a48"


def _lowered_longcat_step() -> str:
    from deepdfa_tpu.config import GGNNConfig
    from deepdfa_tpu.data.synthetic import random_dataset
    from deepdfa_tpu.llm.dataset import GraphJoin, HashTokenizer, encode_functions, text_batches
    from deepdfa_tpu.llm.fusion import FusionModel
    from deepdfa_tpu.llm.joint import JointConfig, JointTrainer
    from deepdfa_tpu.llm.longcat import LongcatModel, tiny_longcat

    cfg = tiny_longcat(vocab_size=256, experts_held=(2, 4))
    llm = LongcatModel(cfg)
    jcfg = JointConfig(block_size=32, train_batch_size=4, eval_batch_size=4, epochs=1,
                       train_llm=False, use_gnn=True)
    graphs = random_dataset(12, seed=0, input_dim=8)
    funcs = [f"int f{i}(int a) {{ return a + {i}; }}" * (1 + i % 3) for i in range(12)]
    examples = encode_functions(
        funcs, [i % 2 for i in range(12)], HashTokenizer(vocab_size=cfg.vocab_size),
        jcfg.block_size, indices=[g.gid for g in graphs])
    fusion = FusionModel(
        gnn_cfg=GGNNConfig(hidden_dim=8, n_steps=2), input_dim=8,
        llm_hidden_size=cfg.hidden_size, use_gnn=True, pool="last")
    ids = jnp.zeros((2, jcfg.block_size), jnp.int32)
    params = nn.meta.unbox(llm.init(jax.random.key(0), ids, jnp.ones(ids.shape, bool))["params"])
    join = GraphJoin.from_list(graphs, max_nodes=512, max_edges=1024)
    trainer = JointTrainer(llm=llm, llm_params=params, fusion=fusion, cfg=jcfg, join=join)
    batch = trainer._joined(next(text_batches(examples, jcfg.train_batch_size)))
    state = trainer._build(3, batch)
    launch = trainer._steps[0]
    jitted = launch.__closure__[launch.__code__.co_freevars.index("jitted_train_step")]
    return jitted.cell_contents.lower(state, params, batch).as_text()


def test_the_longcat_step_is_lowered_as_before():
    """Moved on purpose by PR 34 (from 0683cd6e..., PR 32's program, which PR 33 kept):
    ``held_expert_ffn`` puts a chunk's rows back onto their tokens by a one-hot product
    in place of the scatter-add, and the ``moe`` stats carry one count more
    (``combined``) — both decoders' programs change with it. And by PR 38 (from
    fa1ad548...), which touches no decoder: the counts of the GGNN's view of the graph
    budget leave the step beside the ``moe`` stats."""
    text = _lowered_longcat_step()
    assert hashlib.sha256(text.encode()).hexdigest() == LONGCAT_STEP
