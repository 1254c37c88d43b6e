"""The latent-attention routed-expert decoder (``llm/longcat.py``) against the
plain reference (``benchmark/reference/longcat_fusion.py``) on seeded weights
at a tiny size: hidden states, then loss, first gradient and three-step change
of GGNN + head through ``make_joint_steps(train_llm=False)``; the shares of an
expert-parallel layer add up to the uncut layer; latent attention against
attention over materialised keys and values; the router's cases; pad-mask
invariance; the tiny preset through ``scripts/train_joint.py``; the routing
counts on the trainer's spans."""

import dataclasses
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

from deepdfa_tpu.llm import longcat
from deepdfa_tpu.llm.longcat import (
    ExpertLayer,
    LatentAttention,
    LongcatConfig,
    LongcatModel,
    route,
    tiny_longcat,
)
from deepdfa_tpu.ops.grouped import combine_blocks, held_expert_ffn
from deepdfa_tpu.ops.ring_attention import blocked_causal_attention, full_attention

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "benchmark"
for p in (str(BENCH), str(ROOT / "scripts")):
    if p not in sys.path:
        sys.path.insert(0, p)
TINY_BENCH = BENCH / "tests" / "BENCHMARK.longcat.tiny.json"
CELL = "tiny-longcat-flash-msivd.joint"


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
                llm_cfg=llm_cfg, model=LongcatModel(llm_cfg), params=params, data=data)


def _u(t=24, d=64, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(t, d)), jnp.float32)


def _expert_params(cfg, seed=0, bias_std=0.0):
    layer = ExpertLayer(cfg)
    p = nn.meta.unbox(layer.init(jax.random.key(seed), _u()[None], None)["params"])
    if bias_std:
        p = {**p, "router_bias": bias_std * jax.random.normal(
            jax.random.key(seed + 1), p["router_bias"].shape)}
    return layer, p


# -- the program against the plain reference ---------------------------------


def test_hidden_states_and_routing_match_the_reference(bench):
    rows = np.arange(4)
    ids, mask = bench["data"]["input_ids"][rows], bench["data"]["pad_mask"][rows]
    assert not mask.all() and mask.any(1).all()  # left-padded rows, none empty
    hidden, sown = bench["model"].apply(
        {"params": bench["params"]}, ids, mask, mutable=["routing", "stats"])
    chosen = np.stack([np.asarray(sown["routing"][f"layers_{i}"]["moe"]["choice"][0])
                       for i in range(bench["llm_cfg"].num_layers)])
    # the reference is shown the program's choices and takes none it cannot
    # explain by rounding: its band is the width of its own scores they span
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
    assert counts["dropped"] == 0 and counts["held"] > 0 and counts["zero"] > 0
    assert counts["held"] + counts["zero"] + counts["absent"] == counts["assigned"]
    assert counts["assigned"] == mask.sum() * bench["llm_cfg"].moe_topk * 2  # two layers


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
    assert driver.jcfg.train_llm is False
    t0 = time.time()
    run = driver.run(Phases(t0, driver.setup_steps, 0.0))
    ran = (t0, time.time())  # the ring is the process's: other files' runs leave spans in it too
    ref = bench["reference"].run(bench["cfg"], data, 11, **run["follow"])
    nums = compare.numbers(bench["reference"].COMPARISON, run["readings"], ref)
    return dict(run=run, ref=ref, nums=nums, driver=driver, ran=ran)


@pytest.mark.parametrize("number,limit", [
    ("loss1_gap", 1e-5), ("loss3_gap", 1e-5), ("grad1_gap", 1e-4), ("delta_gap", 1e-3),
    ("hidden_gap", 1e-4), ("pooled_gap", 1e-4), ("logit_gap", 1e-4), ("route_gap", 0.0),
    # the check's forward pass against the timed step's own probs and counts
    ("step_logit_gap", 1e-5), ("step_count_gap", 0.0),
])
def test_train_steps_match_the_reference(followed, number, limit):
    assert followed["nums"][number] <= limit
    assert followed["nums"]["route_agree_share"] > 0.99
    # GGNN and head both trained: every leaf moved, and both sides name the same
    delta = followed["run"]["readings"]["delta"]
    assert set(delta) == set(followed["ref"]["delta"]) and min(delta.values()) > 0
    assert any("flowgnn_encoder" in k for k in delta) and any("classifier" in k for k in delta)


def test_routing_counts_are_on_the_loss_sync_spans(followed):
    t0, t1 = followed["ran"]
    spans = [s for s in followed["driver"].trainer.telemetry.tracer.spans()
             if s.name == "loss.sync" and "moe_held" in s.attrs and t0 <= s.start_s <= t1]
    assert len(spans) >= followed["driver"].setup_steps - 1  # the step in flight is not read
    _, width = combine_blocks(0, followed["driver"].trainer.llm.cfg.moe_chunk_rows)
    for s in spans:
        a = s.attrs
        assert a["moe_dropped"] == 0 and a["reads"] == 1
        assert a["moe_held"] + a["moe_zero"] + a["moe_absent"] == a["moe_assigned"] > 0
        assert a["moe_load_max"] * a["moe_slots"] >= a["moe_held"] * a["moe_layers"]
        assert all(isinstance(a[k], int) for k in a if k.startswith("moe_"))
        # the combine's blocks: only the last one of a layer's loop is not full
        assert a["moe_held"] <= a["moe_combined"] < a["moe_held"] + width * a["moe_layers"]


def test_make_joint_steps_names_no_family():
    import inspect

    from deepdfa_tpu.llm import joint

    src = inspect.getsource(joint.make_joint_steps)
    assert "longcat" not in src.lower() and "llm.apply(" in src


# -- the shares add up --------------------------------------------------------


def test_shares_of_all_ranks_add_up_to_the_uncut_layer(bench):
    """``MoE_here`` of every rank (2 of 8 routed experts each), the
    zero-compute experts counted once, equals the uncut layer — and the plain
    reference's uncut layer."""
    whole = tiny_longcat()
    layer, p = _expert_params(whole, bias_std=0.03)
    u = _u(40)[None]
    mask = jnp.asarray(np.arange(40) >= 5)[None]
    full, counts = layer.apply({"params": p}, u, mask)
    x = u[0]
    choice, gates = route(x, p["router_kernel"], p["router_bias"], whole)
    gates = jnp.where(mask[0][:, None], gates, 0.0)
    zero = longcat._zero_experts(x, gates, (choice >= whole.n_routed_experts))
    total, held = zero, 0
    for r in range(4):
        cfg_r = dataclasses.replace(whole, experts_held=(2 * r, 2 * r + 2))
        p_r = {k: (v[2 * r:2 * r + 2] if k.startswith("experts_") else v) for k, v in p.items()}
        out_r, c_r = ExpertLayer(cfg_r).apply({"params": p_r}, u, mask)
        total = total + (out_r[0] - zero)
        held += int(c_r["held"])
        assert int(c_r["zero"]) == int(counts["zero"]) and int(c_r["dropped"]) == 0
    np.testing.assert_allclose(total, full[0], atol=2e-5)
    assert held == int(counts["held"]) and int(counts["absent"]) == 0
    m = {"moe_topk": 3, "n_routed": 8, "lo": 0, "n_held": 8, "routed_scaling_factor": 6.0}
    ref = bench["reference"]
    w = {"router_kernel": p["router_kernel"], "router_bias": p["router_bias"],
         "experts_gate": p["experts_gate"], "experts_up": p["experts_up"],
         "experts_down": p["experts_down"]}
    plain, *_ = ref._moe(m, lambda a: a, None, 0.0, w, x, mask[0], None)
    np.testing.assert_allclose(plain, full[0], atol=2e-5)


# -- latent attention ---------------------------------------------------------


def test_latent_attention_equals_attention_over_materialised_keys_and_values(bench):
    cfg, ref = bench["llm_cfg"], bench["reference"]
    lw = bench["w"].under("llm/layers_0/attn_1")
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 40, cfg.hidden_size)), jnp.float32)
    mask = jnp.asarray(np.arange(40)[None] >= np.array([[0], [9]]))
    pos = jnp.broadcast_to(jnp.arange(40), (2, 40))
    out = LatentAttention(cfg).apply({"params": unflatten_dict(lw, sep="/")}, x, mask, pos)
    m = ref.model_of(bench["cfg"])
    for r in range(2):  # the reference: scores whole, keys and values materialised per head
        plain = ref._mla(m, lambda a: a, None, lw, x[r], mask[r])
        np.testing.assert_allclose(out[r][mask[r]], plain[mask[r]], atol=2e-4)


@pytest.mark.parametrize("block_q", [8, 16, 64])
def test_blocked_attention_with_a_narrower_value_equals_full_attention(block_q):
    rng = np.random.default_rng(block_q)
    q, k = (jnp.asarray(rng.normal(size=(2, 40, 4, 24)), jnp.float32) for _ in range(2))
    v = jnp.asarray(rng.normal(size=(2, 40, 4, 16)), jnp.float32)
    mask = jnp.asarray(np.arange(40)[None] >= np.array([[0], [13]]))
    want = full_attention(q, k, v, causal=True, kv_mask=mask)
    got = blocked_causal_attention(q, k, v, kv_mask=mask, block_q=block_q)
    assert got.shape == (2, 40, 4, 16)
    np.testing.assert_allclose(got, want, atol=1e-5)


# -- the router ---------------------------------------------------------------


def test_bias_moves_the_choice_and_not_the_weight():
    cfg = tiny_longcat()
    _, p = _expert_params(cfg)
    x = _u(64)
    c0, g0 = route(x, p["router_kernel"], jnp.zeros(cfg.router_width), cfg)
    bias = jnp.zeros(cfg.router_width).at[5].set(10.0)  # expert 5 always chosen
    c1, g1 = route(x, p["router_kernel"], bias, cfg)
    assert (c1 == 5).any(-1).all() and not (c0 == 5).any(-1).all()
    prob = jax.nn.softmax(x @ p["router_kernel"], -1)
    np.testing.assert_allclose(  # the gate is the bare probability times the scale
        g1, cfg.routed_scaling_factor * jnp.take_along_axis(prob, c1, -1), rtol=1e-5)
    assert not np.allclose(g1.sum(-1), cfg.routed_scaling_factor)  # not renormalised


def test_identity_experts_return_their_input_scaled_by_the_gate():
    cfg = tiny_longcat()
    layer, p = _expert_params(cfg)
    # every token to the zero-compute experts 8, 9, 10
    p = {**p, "router_bias": jnp.zeros(cfg.router_width).at[8:11].set(10.0)}
    u = _u(16)[None]
    out, counts = layer.apply({"params": p}, u, None)
    _, gates = route(u[0], p["router_kernel"], p["router_bias"], cfg)
    np.testing.assert_allclose(out[0], gates.sum(-1, keepdims=True) * u[0], rtol=1e-5)
    assert int(counts["zero"]) == 16 * 3 and int(counts["held"]) == 0


@pytest.mark.parametrize("rows", [8, 32, 4096])
def test_every_token_to_one_expert_and_nothing_dropped(rows):
    """No capacity limit: the bias sends every token to experts 1, 4 and 6;
    of the two held (0, 1) expert 1 takes all 48, in one chunk or in six."""
    cfg = tiny_longcat(experts_held=(0, 2), moe_chunk_rows=rows)
    layer, p = _expert_params(cfg)
    p = {**p, "router_bias": jnp.zeros(cfg.router_width).at[jnp.array([1, 4, 6])].set(10.0)}
    u = _u(48)[None]
    out, counts = jax.jit(lambda p_, u_: layer.apply({"params": p_}, u_, None))(p, u)
    assert int(counts["held"]) == 48 and int(counts["load_max"]) == 48
    assert int(counts["dropped"]) == 0 and int(counts["absent"]) == 96
    x = u[0]
    choice, gates = route(x, p["router_kernel"], p["router_bias"], cfg)
    g1 = gates[jnp.arange(48), jnp.argmax(choice == 1, -1)]
    e = jax.nn.silu(x @ p["experts_gate"][1]) * (x @ p["experts_up"][1]) @ p["experts_down"][1]
    np.testing.assert_allclose(out[0], g1[:, None] * e, atol=2e-5)


def test_held_expert_ffn_counts_what_it_computes():
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(30, 16)), jnp.float32)
    choice = jnp.asarray(rng.integers(-1, 12, size=(30, 3)), jnp.int32)
    gates = jnp.asarray(rng.random((30, 3)), jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(4, 16, 8)), jnp.float32) for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(4, 8, 16)), jnp.float32)
    out, computed = held_expert_ffn(u, choice, gates, wg, wu, wd, lo=3, rows=16)
    held = (choice >= 3) & (choice < 7)
    assert int(computed) == int(held.sum()) > 16  # more than one chunk
    want = sum(jnp.where(held & (choice == 3 + e), gates, 0).sum(-1, keepdims=True)
               * ((jax.nn.silu(u @ wg[e]) * (u @ wu[e])) @ wd[e]) for e in range(4))
    np.testing.assert_allclose(out, want, atol=1e-4)


# -- padding ------------------------------------------------------------------


def test_pooled_state_is_invariant_under_left_padding(bench):
    """The last real token's state does not depend on how much padding
    stands before the row, nor on what the padding holds."""
    model, params = bench["model"], bench["params"]
    rng = np.random.default_rng(5)
    body = rng.integers(3, 320, size=20).astype(np.int32)

    def pooled(pad, fill):
        ids = np.concatenate([np.full(pad, fill, np.int32), body])[None]
        mask = (np.arange(pad + 20) >= pad)[None]
        return np.asarray(model.apply({"params": params}, ids, mask))[0, -1]

    base = pooled(0, 1)
    for pad, fill in [(12, 1), (44, 1), (44, 77)]:
        np.testing.assert_allclose(pooled(pad, fill), base, atol=2e-4)


def test_config_reads_the_published_keys_and_refuses_a_bad_range():
    d = json.loads((BENCH / "configs" / "longcat-flash-msivd.json").read_text())
    cfg = LongcatConfig.from_hf_dict({**d, "n_routed_experts": 512})
    assert (cfg.hidden_size, cfg.kv_lora_rank, cfg.q_lora_rank, cfg.moe_topk) == (6144, 512, 1536, 12)
    assert cfg.router_width == 768 and cfg.held == (0, 16) and cfg.num_layers == 4
    assert LongcatConfig().held == (0, 512)
    with pytest.raises(ValueError, match="no range"):
        LongcatConfig(experts_held=(500, 520))
    from deepdfa_tpu.llm.presets import PRESETS

    real = PRESETS["longcat_flash_msivd"]
    assert real.encoder_family == "longcat" and real.llm.held == (0, 16)
    assert real.joint.block_size == 2048 and real.joint.train_batch_size == 4
    assert real.joint.train_llm is False and real.joint.use_gnn and not real.joint.freeze_gnn


def test_expert_weights_carry_the_experts_axis():
    from deepdfa_tpu.llm.llama import LOGICAL_RULES

    cfg = tiny_longcat(experts_held=(2, 4))
    abstract = jax.eval_shape(lambda: LongcatModel(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), jnp.ones((1, 8), bool)))
    specs = nn.get_partition_spec(abstract)["params"]["layers_0"]
    assert specs["moe"]["experts_gate"][0] == "experts"
    mesh_axes = nn.logical_to_mesh(specs, LOGICAL_RULES)
    assert mesh_axes["moe"]["experts_down"][0] is None  # no exchange yet: no mesh axis
    assert mesh_axes["attn_0"]["q_b_proj"]["kernel"] == jax.sharding.PartitionSpec(None, "tp")
    assert abstract["params"]["layers_0"]["moe"]["experts_up"].value.shape == (2, 64, 32)


# -- the normal path ----------------------------------------------------------


def test_tiny_preset_trains_through_train_joint(tmp_path, monkeypatch):
    monkeypatch.setenv("DEEPDFA_STORAGE", str(tmp_path / "storage"))
    import preprocess
    import train_joint

    preprocess.main(["--dataset", "demo", "--sample", "--workers", "1"])
    out = train_joint.main([
        "--preset", "tiny_longcat_msivd", "--dataset", "demo", "--sample", "--do_train",
        "--block_size", "32", "--output_dir", str(tmp_path / "run")])
    assert out["num_missing"] == 0
    epoch = [h for h in out["history"] if "train_loss" in h]
    assert len(epoch) == 1 and np.isfinite(epoch[0]["train_loss"])
    assert epoch[0]["telemetry"]["steps"] > 0
    with pytest.raises(SystemExit, match="contradicts preset"):
        train_joint.main(["--preset", "tiny_longcat_msivd", "--encoder", "llama"])
