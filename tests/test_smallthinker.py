"""The grouped-query decoder of global and windowed layers whose router reads
the layer's input (``llm/smallthinker.py``) against the plain reference
(``benchmark/reference/smallthinker_fusion.py``) on seeded weights at a tiny
size: hidden states and routing of every kind of layer, then the compared
numbers through ``JointTrainer.train`` with the decoder frozen; the halves of
an expert-parallel layer (the one-hot combine) add up to the whole layer (the
gather) and to the uncut reference; left padding, the window and the routing
of pads; every planted fault reads ``correct: false``; the tiny preset through
``scripts/train_joint.py``; the configuration file against the catalog."""

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

from deepdfa_tpu.llm.smallthinker import (
    ExpertLayer,
    SmallThinkerConfig,
    SmallThinkerModel,
    needed_pairs,
    route,
    smallthinker_21b,
    tiny_smallthinker,
)
from deepdfa_tpu.ops.ring_attention import blocked_key_ranges

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "benchmark"
for p in (str(BENCH), str(BENCH / "tools"), str(ROOT / "scripts")):
    if p not in sys.path:
        sys.path.insert(0, p)
TINY_BENCH = BENCH / "tests" / "BENCHMARK.smallthinker.tiny.json"
CELL = "tiny-smallthinker-21b-msivd.joint"
COMPARED = ("grad1_gap", "delta_gap", "hidden_gap", "route_gap", "step_logit_gap",
            "step_count_gap")
PERIOD = [0, 1, 1, 1]
# the catalog row's ``config`` (model-configs guide, architectures.jsonl, SmallThinker-21BA3B-Instruct)
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True, "num_attention_heads": 28,
    "num_hidden_layers": 52, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": PERIOD * 13, "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": PERIOD * 13, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}


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
                llm_cfg=llm_cfg, model=SmallThinkerModel(llm_cfg), params=params, data=data)


def _u(t=24, d=64, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(t, d)), jnp.float32)


def _expert_params(cfg, seed=0):
    layer = ExpertLayer(cfg)
    x = _u()[None]
    return layer, nn.meta.unbox(layer.init(jax.random.key(seed), x, x, None)["params"])


# -- the program against the plain reference ---------------------------------


def test_hidden_states_and_routing_match_the_reference(bench):
    lengths = bench["data"]["lengths"]
    rows = np.concatenate([np.flatnonzero(lengths > 40)[:2], np.flatnonzero(lengths < 20)[:2]])
    ids, mask = bench["data"]["input_ids"][rows], bench["data"]["pad_mask"][rows]
    cfg = bench["llm_cfg"]
    assert not mask.all() and mask.any(1).all() and mask.sum(1).max() > cfg.sliding_window_size
    hidden, sown = bench["model"].apply(
        {"params": bench["params"]}, ids, mask, mutable=["routing", "stats"])
    chosen = np.stack([np.asarray(sown["routing"][f"layers_{i}"]["moe"]["choice"][0])
                       for i in range(cfg.num_hidden_layers)])
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
    k = cfg.moe_num_active_primary_experts
    assert counts["dropped"] == 0 and counts["zero"] == 0 and counts["absent"] == 0
    assert counts["held"] == counts["assigned"] == counts["gathered"] == mask.sum() * k * 8
    assert counts["combined"] == 0 and counts["layers"] == 8 and counts["slots"] == 8 * 8
    attn = jax.device_get(sown["stats"]["attn"])
    assert attn["layers"] == 8 and attn["window_layers"] == 6  # global at 0 and 4
    per_row = lambda n, w: n * (n + 1) // 2 if w is None or n <= w else (
        w * (w + 1) // 2 + (n - w) * w)
    assert attn["pairs_needed"] == sum(
        2 * per_row(int(n), None) + 6 * per_row(int(n), 24) for n in mask.sum(1))
    s = mask.shape[1]
    blocks = lambda w: 4 * sum((e - a) * (e - lo) for a, e, lo in blocked_key_ranges(s, 16, w))
    assert attn["pairs_computed"] == 2 * blocks(None) + 6 * blocks(24) > attn["pairs_needed"]
    assert bench["drivers"].needed_pairs(mask, 24) == int(needed_pairs(jnp.asarray(mask), 24))


@pytest.mark.parametrize("layer,rope,window", [(0, False, None), (1, True, 24), (4, False, None),
                                               (7, True, 24)])
def test_every_kind_of_layer_matches_the_reference(bench, layer, rope, window):
    """One layer alone, global without RoPE or windowed with it, on rows that
    reach past the window, left-padded: the reference's ``_layer`` over the
    same weights."""
    from deepdfa_tpu.llm.smallthinker import SmallThinkerLayer

    cfg, ref = bench["llm_cfg"], bench["reference"]
    assert (bool(cfg.rope_layout[layer]), cfg.window(layer)) == (rope, window)
    h = jnp.asarray(np.random.default_rng(layer).normal(size=(2, 48, 64)), jnp.float32)
    mask = jnp.asarray(np.arange(48)[None] >= np.array([[0], [11]]))
    positions = jnp.maximum(jnp.cumsum(mask, -1) - 1, 0)
    lw = bench["w"].under(f"llm/layers_{layer}")
    (out, _), sown = SmallThinkerLayer(cfg, rope, window).apply(
        {"params": bench["params"][f"layers_{layer}"]}, h, mask, positions, mutable=["routing"])
    choice = sown["routing"]["moe"]["choice"][0]
    want, used, own, band = ref._layer(
        ref.model_of(bench["cfg"]), "f32", None, 1e-5, rope, window is not None, lw, h, mask,
        choice)
    m = np.asarray(mask)
    np.testing.assert_allclose(np.asarray(out)[m], np.asarray(want)[m], atol=2e-5)
    assert np.asarray(band).max() < 1e-5 and np.array_equal(np.asarray(used)[m], np.asarray(choice)[m])


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
    assert driver.jcfg.train_llm is False and isinstance(driver.trainer.llm, SmallThinkerModel)
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


def test_routing_and_attention_counts_are_on_the_loss_sync_spans(followed):
    t0, t1 = followed["ran"]
    spans = [s for s in followed["driver"].trainer.telemetry.tracer.spans()
             if s.name == "loss.sync" and "moe_gathered" in s.attrs and t0 <= s.start_s <= t1]
    assert len(spans) >= followed["driver"].setup_steps - 1  # the step in flight is not read
    jcfg, cfg = followed["driver"].jcfg, followed["driver"].llm_cfg
    every_slot = (cfg.num_hidden_layers * jcfg.train_batch_size * jcfg.block_size
                  * cfg.moe_num_active_primary_experts)
    for s in spans:
        a = s.attrs
        assert a["moe_dropped"] == a["moe_zero"] == a["moe_absent"] == a["moe_combined"] == 0
        assert a["moe_held"] == a["moe_assigned"] == a["moe_gathered"] > 0 and a["moe_layers"] == 8
        # the token rows the gather visited times k, beside the assignments it combined
        assert 0 < a["moe_assigned"] <= a["moe_gather_slots"] <= every_slot
        assert a["attn_layers"] == 8 and a["attn_window_layers"] == 6
        assert 0 < a["attn_pairs_needed"] < a["attn_pairs_computed"]
    tie = followed["run"]["readings"]["tie"]
    assert {"moe_gathered", "attn_pairs_needed", "attn_window_layers"} <= set(tie["counts"][0])
    assert followed["run"]["readings"]["routing"][0].shape[0] == 8  # every layer routes
    counters = followed["run"]["counters"]
    assert counters.get("steps", 0) == 0 or counters["attn_pairs_window"] <= counters["attn_pairs_global"]


# -- the shares add up --------------------------------------------------------


def test_the_halves_add_up_to_the_whole_layer_and_to_the_uncut_reference(bench):
    """``held=(0, 4)`` and ``held=(4, 8)`` (the one-hot combine: half the
    router is absent on each) summed equal the layer that holds all 8 (the
    gather) and the plain reference's with every expert held."""
    whole = tiny_smallthinker()
    layer, p = _expert_params(whole)
    n, m = _u(40, seed=1)[None], _u(40, seed=2)[None]
    mask = jnp.asarray(np.arange(40) >= 5)[None]
    full, counts = layer.apply({"params": p}, n, m, mask)
    assert whole.holds_every_expert and int(counts["gathered"]) == int(counts["held"]) == 35 * 3
    total, held = 0.0, 0
    for lo in (0, 4):
        cfg_r = dataclasses.replace(whole, experts_held=(lo, lo + 4))
        p_r = {k: (v[lo:lo + 4] if k.startswith("experts_") else v) for k, v in p.items()}
        out_r, c_r = ExpertLayer(cfg_r).apply({"params": p_r}, n, m, mask)
        assert not cfg_r.holds_every_expert and int(c_r["gathered"]) == 0
        assert int(c_r["combined"]) >= int(c_r["held"]) > 0 and int(c_r["dropped"]) == 0
        assert int(c_r["held"]) + int(c_r["absent"]) == int(counts["assigned"])
        total, held = total + out_r[0], held + int(c_r["held"])
    np.testing.assert_allclose(total, full[0], atol=2e-5)
    assert held == int(counts["held"]) and int(counts["absent"]) == 0
    ref = bench["reference"]
    sizes = {"moe_num_active_primary_experts": 3, "lo": 0, "n_held": 8}
    w = {k: p[k] for k in ("router_kernel", "experts_gate", "experts_up", "experts_down")}
    plain, *_ = ref._moe(sizes, lambda a: a, None, 0.0, w, n[0], m[0], mask[0], None)
    np.testing.assert_allclose(plain, full[0], atol=2e-5)


def test_the_router_reads_its_own_input_and_its_gates_sum_to_one():
    cfg = tiny_smallthinker()
    layer, p = _expert_params(cfg)
    n, m = _u(32, seed=3), _u(32, seed=4)
    choice, gates = route(n, p["router_kernel"], cfg)
    assert choice.shape == gates.shape == (32, 3)
    np.testing.assert_allclose(gates.sum(-1), 1.0, rtol=1e-6)
    logits = np.asarray(n @ p["router_kernel"])
    top = np.sort(logits, -1)[:, ::-1][:, :3]
    np.testing.assert_allclose(np.sort(np.asarray(gates), -1)[:, ::-1],
                               np.exp(top) / np.exp(top).sum(-1, keepdims=True), rtol=1e-5)
    # the experts' input changes what they compute, never who is chosen
    _, sown_a = layer.apply({"params": p}, n[None], m[None], None, mutable=["routing"])
    _, sown_b = layer.apply({"params": p}, n[None], 2 * m[None], None, mutable=["routing"])
    assert np.array_equal(sown_a["routing"]["choice"][0], sown_b["routing"]["choice"][0])
    assert np.array_equal(np.asarray(sown_a["routing"]["choice"][0])[0], np.asarray(choice))


def test_pads_are_routed_nowhere_and_nothing_is_dropped_with_every_token_on_one_expert():
    cfg = tiny_smallthinker(moe_num_active_primary_experts=1)
    layer, p = _expert_params(cfg)
    p = {**p, "router_kernel": jnp.zeros((64, 8)).at[:, 5].set(1.0)}
    n = jnp.abs(_u(48, seed=5))[None] + 0.1  # every logit of expert 5 is the largest
    mask = jnp.asarray(np.arange(48) >= 9)[None]
    (out, counts), sown = layer.apply({"params": p}, n, n, mask, mutable=["routing"])
    choice = np.asarray(sown["routing"]["choice"][0])[0]
    assert (choice[:9] == -1).all() and (choice[9:] == 5).all()
    assert int(counts["held"]) == int(counts["load_max"]) == 39 > cfg.moe_chunk_rows  # two trips
    assert int(counts["dropped"]) == 0 and not np.asarray(out[0, :9]).any()
    x = n[0, 20]
    one = (jax.nn.relu(x @ p["experts_gate"][5]) * (x @ p["experts_up"][5])) @ p["experts_down"][5]
    np.testing.assert_allclose(out[0, 20], one, rtol=1e-4, atol=1e-5)


# -- padding and the window ---------------------------------------------------


def _states(bench, pad, fill=1, model=None, n=40):
    body = np.random.default_rng(5).integers(3, 320, size=n).astype(np.int32)
    ids = np.concatenate([np.full(pad, fill, np.int32), body])[None]
    mask = (np.arange(pad + n) >= pad)[None]
    return np.asarray((model or bench["model"]).apply({"params": bench["params"]}, ids, mask))[0, pad:]


@pytest.mark.parametrize("pad,fill", [(8, 1), (24, 1), (24, 77)])
def test_left_padding_changes_no_real_tokens_state(bench, pad, fill):
    """Window, RoPE positions and routing all start at the first real token:
    every real token of a 40-token row (longer than the window of 24) reads
    what the row alone reads, whatever the pads hold."""
    np.testing.assert_allclose(_states(bench, pad, fill), _states(bench, 0), atol=2e-4)


def test_a_row_longer_than_the_window_differs_from_the_same_row_without_one(bench):
    cfg = bench["llm_cfg"]
    wide = SmallThinkerModel(dataclasses.replace(cfg, sliding_window_size=4096))
    with_window, without = _states(bench, 0), _states(bench, 0, model=wide)
    np.testing.assert_allclose(with_window[:24], without[:24], atol=1e-5)  # inside the window: the same
    gap = np.linalg.norm(with_window[24:] - without[24:], axis=-1) / np.linalg.norm(without[24:], axis=-1)
    assert gap.min() > 1e-3
    # and a row no longer than the window reads the same with and without it
    np.testing.assert_allclose(_states(bench, 0, n=24), _states(bench, 0, model=wide, n=24), atol=1e-5)


# -- configuration ------------------------------------------------------------


def test_config_reads_the_published_keys_and_refuses_what_it_cannot_build():
    cfg = SmallThinkerConfig.from_hf_dict(PUBLISHED)
    assert cfg == smallthinker_21b() and cfg.held == (0, 64) and cfg.holds_every_expert
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim) == (
        2560, 28, 4, 128)
    assert cfg.window(0) is None and cfg.window(1) == cfg.window(3) == 4096 and cfg.window(4) is None
    assert cfg.window_layers == 39 and cfg.n_routed_experts == 64
    assert not dataclasses.replace(cfg, experts_held=(0, 32)).holds_every_expert
    with pytest.raises(ValueError, match="no range"):
        SmallThinkerConfig(experts_held=(60, 70))
    with pytest.raises(ValueError, match="another router"):
        SmallThinkerConfig(norm_topk_prob=False)
    with pytest.raises(ValueError, match="does not say"):
        SmallThinkerConfig(rope_layout=(0, 1, 1, 1))
    with pytest.raises(ValueError, match="rope_scaling"):
        SmallThinkerConfig(rope_scaling={"type": "yarn"})
    from deepdfa_tpu.llm.families import FAMILIES, build_encoder
    from deepdfa_tpu.llm.presets import PRESETS

    real, small = PRESETS["smallthinker_21b_msivd"], PRESETS["tiny_smallthinker_msivd"]
    assert real.encoder_family == small.encoder_family == "smallthinker"
    assert real.llm == smallthinker_21b(num_hidden_layers=12, experts_held=(0, 64))
    assert real.joint.block_size == 8192 and real.joint.train_batch_size == 2
    assert real.joint.learning_rate == 1e-6 and real.dataset == "precisebugs"
    assert real.joint.train_llm is False and real.joint.use_gnn and not real.joint.freeze_gnn
    assert small.llm == tiny_smallthinker(vocab_size=2048)
    fam = FAMILIES["smallthinker"]
    llm, params, _, got = build_encoder(fam, None, 16)
    assert isinstance(llm, SmallThinkerModel) and got == tiny_smallthinker(vocab_size=2048)
    assert fam.pool == "last" and fam.trained is False and fam.from_checkpoint is None
    assert params["layers_0"]["moe"]["router_kernel"].dtype == jnp.float32
    assert not any(isinstance(x, nn.Partitioned) for x in jax.tree.leaves(
        params, is_leaf=lambda x: isinstance(x, nn.Partitioned)))


def test_the_configuration_file_holds_the_catalog_entry_but_for_the_depth():
    d = json.loads((BENCH / "configs" / "smallthinker-21b-msivd.json").read_text())
    assert {k for k, v in PUBLISHED.items() if d.get(k, "absent") != v} == set(d["reduced"]) == {
        "num_hidden_layers"}
    assert d["published"] == {"num_hidden_layers": 52}
    assert d["num_hidden_layers"] % 4 == 0 and d["num_hidden_layers"] >= 8  # whole periods
    assert d["experts_held"] == [0, 64] and d["ep_chips"] == 1
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == d["name"])
    assert entry["reduced"] == d["reduced"] and entry["source"] == d["source"]
    assert entry["source"] == (
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json")
    assert (d["entry"], d["reference"], d["flops"]) == (
        "joint_trainer_frozen_smallthinker", "smallthinker_fusion", "smallthinker_fusion_train")
    from deepdfa_tpu.llm.presets import PRESETS
    from harness import spec

    drivers = spec.load_module("drivers", d["entry"])
    # the driver's class adds a name to the preset's: the same fields
    assert dataclasses.asdict(drivers.model_config(d)) == dataclasses.asdict(
        PRESETS["smallthinker_21b_msivd"].llm)
    train = d["train"]
    assert (train["block_size"], train["train_batch_size"]) == (8192, 2)
    assert d["graph_join"] == {"max_nodes": 8192, "max_edges": 17408}
    assert set(d["limits"]) == set(COMPARED) <= set(d["limit_reasons"])
    assert d["check"]["labels"] == "all_negative" and d["check"]["rows"] == "one_longer_than_window"
    # frozen bytes as the file states them: a layer 0.797 GB, 12 of them and the embedding 10.35 GB
    attn = 2560 * (28 * 128 + 2 * 4 * 128) + 28 * 128 * 2560
    layer = 2 * (attn + 64 * 3 * 2560 * 768 + 2 * 2560) + 4 * 2560 * 64
    assert round(attn / 1e6, 2) == 20.97 and round(layer / 1e9, 3) == 0.798
    frozen = d["num_hidden_layers"] * layer + 2 * (151936 * 2560 + 2560)
    assert round(frozen / 1e9, 2) == {12: 10.35, 8: 7.16}[d["num_hidden_layers"]]


def test_weights_carry_their_logical_axes():
    from deepdfa_tpu.llm.llama import LOGICAL_RULES

    cfg = tiny_smallthinker(experts_held=(2, 4))
    abstract = jax.eval_shape(lambda: SmallThinkerModel(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), jnp.ones((1, 8), bool)))
    specs = nn.get_partition_spec(abstract)["params"]["layers_1"]
    assert specs["moe"]["experts_gate"][0] == "experts"
    mesh_axes = nn.logical_to_mesh(specs, LOGICAL_RULES)
    P = jax.sharding.PartitionSpec
    assert mesh_axes["moe"]["experts_down"][0] is None  # no exchange yet: no mesh axis
    assert mesh_axes["attn"]["q_proj"]["kernel"] == P("fsdp", "tp")
    assert nn.meta.unbox(abstract)["params"]["layers_1"]["moe"]["experts_up"].shape == (2, 64, 32)


# -- planted faults -----------------------------------------------------------


@pytest.fixture(scope="module")
def tiny(bench):
    from harness import traffic

    cfg, reference = bench["cfg"], bench["reference"]
    data = traffic.generate(bench["cell"]["cell"]["traffic"], 5, {"n_examples": 64})
    rows = np.argsort(-data["lengths"], kind="stable")[:12]  # rows that reach past the window
    follow = {"step_rows": [rows[:4], rows[4:8], rows[8:]], "total_steps": 100}
    return cfg, reference, data, follow, reference.run(cfg, data, 5, **follow)


def test_reference_against_itself(tiny):
    from harness import compare

    cfg, reference, data, follow, ref = tiny
    again = reference.run(cfg, data, 5, **follow, routing=ref["routing"])
    nums = compare.numbers(reference.COMPARISON, again, ref)
    assert nums["hidden_gap"] == 0 and nums["route_agree_share"] == 1.0
    assert nums["grad1_gap"] == 0 and nums["delta_gap"] == 0
    assert ref["routing"][0].shape[0] == 8  # every layer routes
    w = reference.make_weights(cfg, 5)
    k = np.asarray(w["llm/layers_1/attn/q_proj/kernel"])
    assert np.array_equal(k, k.astype("bfloat16").astype(np.float32)) and k.std() > 0
    assert w["llm/layers_3/moe/experts_down"].shape == (8, 32, 64)


@pytest.mark.parametrize("control", [
    "fp8", "half_batch", "state_unchanged", "window_dropped", "rope_on_global", "rope_dropped",
    "router_reads_m", "silu_for_relu", "sigmoid_gates", "expert_skipped", "softmax_all"])
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


@pytest.mark.parametrize("kind", [
    "window_dropped", "rope_on_global", "rope_dropped", "router_reads_m", "silu_for_relu",
    "sigmoid_gates", "softmax_all", "expert_skipped", "count_off"])
def test_correct_is_false_with_the_fault_planted_in_the_program(kind, monkeypatch, capsys):
    import prove_frozen_smallthinker

    prove_frozen_smallthinker.plant(kind, monkeypatch.setattr)
    row, over = _last_row(capsys)
    assert row["correct"] is False and over
    if kind == "count_off":  # the check's own pass counts wrongly too: only the span differs
        assert over == {"step_count_gap"}
    else:
        assert "hidden_gap" in over
    if kind == "router_reads_m":  # another router: the choices part, not only the states
        assert "route_gap" in over


def test_correct_is_false_with_an_expert_skipped_in_the_timed_step_alone(monkeypatch, capsys):
    """The check's own forward pass stays good, so the numbers that read it
    pass; what ties it to the timed step does not."""
    import prove_frozen_smallthinker
    from harness import spec

    drivers = spec.load_module("drivers", "joint_trainer_frozen_smallthinker")
    real_load = drivers.Driver.load

    def load(self, *a):
        real_load(self, *a)
        prove_frozen_smallthinker.prove_frozen.step_alone(self, "expert_skipped")

    monkeypatch.setattr(drivers.Driver, "load", load)
    row, over = _last_row(capsys)
    assert row["correct"] is False
    assert over & {"step_logit_gap", "step_count_gap"}, row["compared"]
    assert not over & {"hidden_gap", "route_gap"}, row["compared"]


# -- the normal path ----------------------------------------------------------


def test_tiny_preset_trains_through_train_joint(tmp_path, monkeypatch):
    monkeypatch.setenv("DEEPDFA_STORAGE", str(tmp_path / "storage"))
    import preprocess
    import train_joint

    preprocess.main(["--dataset", "demo", "--sample", "--workers", "1"])
    out = train_joint.main([
        "--preset", "tiny_smallthinker_msivd", "--dataset", "demo", "--sample", "--do_train",
        "--block_size", "32", "--output_dir", str(tmp_path / "run")])
    assert out["num_missing"] == 0
    epoch = [h for h in out["history"] if "train_loss" in h]
    assert len(epoch) == 1 and np.isfinite(epoch[0]["train_loss"])
    assert epoch[0]["telemetry"]["steps"] >= 2
    with pytest.raises(SystemExit, match="contradicts preset"):
        train_joint.main(["--preset", "tiny_smallthinker_msivd", "--encoder", "longcat"])
