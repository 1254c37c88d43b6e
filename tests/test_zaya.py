"""The decoder of compressed convolutional attention and top-1 MLP-routed
experts (``llm/zaya.py``, ``ops/cca.py``) against the plain reference
(``benchmark/reference/zaya_fusion.py``) on seeded weights at a tiny size
(hidden 64, 4 | 2 heads of 16, 4 layers, 8 experts and the skip, a router 16
wide): hidden states and routing, one layer with the router state of the layer
before, then the compared numbers through ``JointTrainer.train`` with the
decoder frozen; left padding; each part of the layer seen when it is taken
away; the halves of an expert-parallel layer adding up to the whole with the
skip counted once; the control and every fault of the reference read
``correct: false`` (the program's plantings through ``run.py``, in the step
and in the timed step alone, are ``benchmark/tests/test_zaya_frozen.py``'s);
the preset, ``FAMILIES`` and ``scripts/train_joint.py``; the configuration
file against the catalog."""

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

from deepdfa_tpu.llm.zaya import (
    ZayaConfig,
    ZayaExperts,
    ZayaLayer,
    ZayaModel,
    route,
    tiny_zaya,
    zaya1_8b,
)

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "benchmark"
for p in (str(BENCH), str(BENCH / "tools"), str(ROOT / "scripts")):
    if p not in sys.path:
        sys.path.insert(0, p)
TINY_BENCH = BENCH / "tests" / "BENCHMARK.zaya.tiny.json"
CELL = "tiny-zaya1-8b-msivd.joint"
COMPARED = ("grad1_gap", "delta_gap", "hidden_gap", "route_gap", "step_logit_gap",
            "step_count_gap")
# the parts of the layer a planting takes away (benchmark/tools/prove_frozen_zaya.py)
PARTS = ("depthwise_conv_dropped", "grouped_conv_dropped", "value_shift_dropped",
         "qk_mean_dropped", "temperature_dropped", "rope_whole_head", "eda_dropped",
         "skip_never_taken", "expert_skipped")
# the catalog row's ``config`` (model-configs guide, architectures.jsonl, ZAYA1-8B)
PUBLISHED = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "layer_types": ["hybrid"] * 40,
    "lm_head_bias": False, "max_position_embeddings": 131072, "model_type": "zaya",
    "moe_intermediate_size": 2048, "num_attention_heads": 8, "num_experts": 16,
    "num_experts_per_tok": 1, "num_hidden_layers": 40, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000, "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"},
    "router_hidden_size": 256, "sliding_window": None, "tie_word_embeddings": True,
    "vocab_size": 262272}


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
                llm_cfg=llm_cfg, params=params, data=data, apply=_jitted(llm_cfg, params))


def _jitted(cfg, params):
    """The model applied under ``jit`` (eager, the experts' loops dispatch op
    by op): ``(ids, mask) -> (states, {"routing", "stats"})``. Made anew
    where a planting must be traced."""
    model = ZayaModel(cfg)
    run = jax.jit(lambda p, ids, mask: model.apply(
        {"params": p}, ids, mask, mutable=["routing", "stats"]))
    return lambda ids, mask: run(params, ids, mask)


def _rows(bench, n=4):
    lengths = bench["data"]["lengths"]
    rows = np.concatenate([np.flatnonzero(lengths > 40)[:n // 2], np.flatnonzero(lengths < 20)[:n // 2]])
    return bench["data"]["input_ids"][rows], bench["data"]["pad_mask"][rows]


def _gap(a, b, mask):
    a, b = np.asarray(a)[mask], np.asarray(b)[mask]
    return np.linalg.norm(a - b, axis=-1) / np.linalg.norm(b, axis=-1)


# -- the program against the plain reference ---------------------------------


def test_hidden_states_and_routing_match_the_reference(bench):
    """Both sides float32 here: they part by summation order alone (~1e-6 of a
    state), so 1e-4 is room for that and for nothing else."""
    ids, mask = _rows(bench)
    cfg = bench["llm_cfg"]
    assert not mask.all() and mask.any(1).all()
    hidden, sown = bench["apply"](ids, mask)
    chosen = np.stack([np.asarray(sown["routing"][f"layers_{i}"]["moe"]["choice"][0])
                       for i in range(cfg.num_hidden_layers)])
    ref_h, used, own, band = bench["reference"].decoder(
        bench["cfg"], bench["w"], ids, mask, routing=chosen)
    assert _gap(hidden, ref_h, mask).max() < 1e-4
    assert np.asarray(band).max() < bench["cfg"]["check"]["route_epsilon"]
    agree = chosen[..., 0] == np.asarray(own)[..., 0]
    assert agree[:, mask].all() and (chosen[:, ~mask] == -1).all()  # a pad is routed nowhere
    counts = jax.device_get(sown["stats"]["moe"])
    skips = int((chosen == cfg.num_experts).sum())
    assert skips > 0 and counts["zero"] == skips and counts["absent"] == counts["dropped"] == 0
    assert counts["assigned"] == mask.sum() * 4 and counts["held"] + skips == counts["assigned"]
    assert counts["gathered"] == counts["held"] and counts["combined"] == 0
    cca = jax.device_get(sown["stats"]["cca"])
    assert cca["layers"] == 4 and cca["fused"] == 0  # the CPU: the blocked attention
    attn = jax.device_get(sown["stats"]["attn"])
    assert attn["pairs_needed"] == 4 * sum(int(n) * (int(n) + 1) // 2 for n in mask.sum(1))
    assert attn["pairs_computed"] > attn["pairs_needed"]


def test_one_layer_with_the_router_state_of_the_layer_before_matches_the_reference(bench):
    """Layer 1 alone, left-padded rows, handed an EDA state: the reference's
    ``_layer`` over the same weights, the same state handed on."""
    cfg, ref = bench["llm_cfg"], bench["reference"]
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.normal(size=(2, 48, 64)), jnp.float32)
    prev = jnp.asarray(rng.normal(size=(96, 16)), jnp.float32)
    mask = jnp.asarray(np.arange(48)[None] >= np.array([[0], [11]]))
    positions = jnp.maximum(jnp.cumsum(mask, -1) - 1, 0)
    (out, r, _), sown = jax.jit(lambda *a: ZayaLayer(cfg).apply(
        {"params": bench["params"]["layers_1"]}, *a, mutable=["routing"]))(h, mask, positions, prev)
    choice = sown["routing"]["moe"]["choice"][0]
    want, want_r, used, own, band = ref._layer(
        ref.model_of(bench["cfg"]), "f32", None, 1e-5, bench["w"].under("llm/layers_1"), h, mask,
        prev, choice)
    m = np.asarray(mask)
    np.testing.assert_allclose(np.asarray(out)[m], np.asarray(want)[m], atol=2e-5)
    np.testing.assert_allclose(np.asarray(r), np.asarray(want_r), rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(used)[m], np.asarray(choice)[m])


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
    assert driver.jcfg.train_llm is False and isinstance(driver.trainer.llm, ZayaModel)
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
    assert followed["nums"]["route_agree_share"] == 1.0
    delta = followed["run"]["readings"]["delta"]
    assert set(delta) == set(followed["ref"]["delta"]) and min(delta.values()) > 0


def test_the_counts_are_on_the_loss_sync_spans(followed):
    t0, t1 = followed["ran"]
    spans = [s for s in followed["driver"].trainer.telemetry.tracer.spans()
             if s.name == "loss.sync" and "cca_layers" in s.attrs and t0 <= s.start_s <= t1]
    assert len(spans) >= followed["driver"].setup_steps - 1  # the step in flight is not read
    for s in spans:
        a = s.attrs
        assert a["moe_dropped"] == a["moe_absent"] == a["moe_combined"] == 0
        assert a["moe_held"] + a["moe_zero"] == a["moe_assigned"] and a["moe_zero"] > 0
        assert a["moe_gathered"] == a["moe_held"] and a["moe_layers"] == 4
        assert a["cca_layers"] == 4 and a["cca_fused"] == 0
        assert 0 < a["attn_pairs_needed"] < a["attn_pairs_computed"]
    tie = followed["run"]["readings"]["tie"]
    assert {"moe_gathered", "moe_zero", "cca_layers", "attn_pairs_needed"} <= set(tie["counts"][0])
    assert followed["run"]["readings"]["routing"][0].shape == (4, 4, 64, 1)  # every layer, top-1
    counters = followed["run"]["counters"]
    assert counters.get("steps", 0) == 0 or counters["attn_pairs_global"] > 0


# -- padding and the parts of the layer ---------------------------------------


@pytest.fixture(scope="module")
def padded(bench):
    """``{(first, fill): (states in the batch, states alone)}``: the real
    tokens of the rows of a batch of three whose first real tokens sit at
    positions 0, 1 and 37 of 77 (left padding: each row's tokens end the
    block, the pads hold ``fill``), and the same tokens as a batch of their
    own with no pad."""
    body = np.random.default_rng(5).integers(3, 320, size=77).astype(np.int32)
    firsts, out = (0, 1, 37), {}
    states = lambda ids, mask: np.asarray(bench["apply"](ids, mask)[0])
    for fill in (1, 77):
        ids = np.stack([np.where(np.arange(77) >= f, body, fill) for f in firsts]).astype(np.int32)
        batch = states(ids, np.arange(77)[None] >= np.array(firsts)[:, None])
        for r, f in enumerate(firsts):
            alone = states(body[None, f:], np.ones((1, 77 - f), bool))[0]
            out[f, fill] = batch[r, f:], alone
    return out


@pytest.mark.parametrize("first", [0, 1, 37])
@pytest.mark.parametrize("fill", [1, 77])
def test_left_padding_changes_no_real_tokens_state(padded, first, fill):
    """The convolutions, the value shift, RoPE's positions and the routing all
    start at a row's first real token: its real tokens read what they read
    as a row of their own, whatever the pads hold."""
    batch, alone = padded[first, fill]
    np.testing.assert_allclose(batch, alone, atol=2e-5)


@pytest.mark.parametrize("kind", PARTS)
def test_each_part_of_the_layer_is_seen_when_it_is_taken_away(bench, kind, monkeypatch):
    """Both convolutions, the value shift, qk-mean, the temperature, the
    partial rotation, EDA, the skip and one expert: taking any away from a
    layer handed the layer before's router state moves its real tokens'
    output well past float32's rounding."""
    import prove_frozen_zaya

    cfg, p = bench["llm_cfg"], bench["params"]["layers_1"]
    rng = np.random.default_rng(9)
    h = jnp.asarray(rng.normal(size=(2, 48, 64)), jnp.float32)
    prev = jnp.asarray(rng.normal(size=(96, 16)), jnp.float32)
    mask = np.arange(48)[None] >= np.array([[0], [11]])
    positions = jnp.maximum(jnp.cumsum(mask, -1) - 1, 0)
    layer = lambda: jax.jit(lambda *a: ZayaLayer(cfg).apply({"params": p}, *a)[0])(
        h, jnp.asarray(mask), positions, prev)  # a fresh jit: traced with what is planted
    good = layer()
    prove_frozen_zaya.plant(kind, monkeypatch.setattr)
    assert _gap(layer(), good, mask).max() > 1e-2


# -- the experts, the skip and the router -------------------------------------


def _choices(cfg, t=40, seed=3):
    rng = np.random.default_rng(seed)
    choice = rng.integers(0, cfg.num_experts + 1, size=(t, 1)).astype(np.int32)
    choice[:5] = -1  # left pads
    gate = np.where(choice >= 0, rng.uniform(0.05, 0.5, size=(t, 1)), 0.0).astype(np.float32)
    return jnp.asarray(choice), jnp.asarray(gate)


def test_the_halves_add_up_to_the_whole_layer_with_the_skip_counted_once(bench):
    """``held=(0, 4)`` and ``held=(4, 8)`` (the one-hot combine, half the
    experts absent on each) each add the skip's ``g m`` as every chip would;
    their sum with the skip counted once equals the layer that holds all 8
    (the gather), and the plain reference's with every expert held."""
    whole = tiny_zaya()
    m = jnp.asarray(np.random.default_rng(2).normal(size=(1, 40, 64)), jnp.float32)
    choice, gate = _choices(whole)
    layer = ZayaExperts(whole)
    p = nn.meta.unbox(layer.init(jax.random.key(0), m, choice, gate)["params"])
    full, counts = layer.apply({"params": p}, m, choice, gate)
    skip = (choice == whole.num_experts)
    assert whole.holds_every_expert and int(skip.sum()) > 0
    assert int(counts["zero"]) == int(skip.sum()) and int(counts["gathered"]) == int(counts["held"])
    skip_part = np.asarray(jnp.where(skip, gate, 0.0) * m[0])
    total, held = -skip_part, 0
    for lo in (0, 4):
        cfg_r = dataclasses.replace(whole, experts_held=(lo, lo + 4))
        p_r = {k: v[lo:lo + 4] for k, v in p.items()}
        out_r, c_r = ZayaExperts(cfg_r).apply({"params": p_r}, m, choice, gate)
        assert not cfg_r.holds_every_expert and int(c_r["gathered"]) == 0
        assert int(c_r["zero"]) == int(counts["zero"]) and int(c_r["dropped"]) == 0
        assert int(c_r["held"]) + int(c_r["absent"]) + int(c_r["zero"]) == int(counts["assigned"])
        total, held = total + np.asarray(out_r[0]), held + int(c_r["held"])
    np.testing.assert_allclose(total, full[0], atol=2e-5)
    assert held == int(counts["held"]) and int(counts["absent"]) == 0
    # the plain reference's expert loop over the same choices (its own route taken by the program's)
    ref = bench["reference"]
    sizes = {"lo": 0, "n_held": 8, "num_experts": 8}
    w = {"down": jnp.zeros((64, 16)), "mlp_1": jnp.zeros((16, 16)), "mlp_2": jnp.zeros((16, 16)),
         "mlp_3": jnp.zeros((16, 9)), "bias": jnp.zeros(9), **p}
    real = choice[:, 0] >= 0
    plain, *_ = ref._moe(sizes, None, 1e9, w, m[0], real, None, jnp.maximum(choice, 0),
                         lambda a: a)
    # the reference's gate is softmax(0) = 1/9 where the program's is drawn: compare per gate
    np.testing.assert_allclose(plain * 9.0 * np.asarray(gate), full[0] * (np.asarray(real)[:, None]),
                               atol=2e-5)


def test_the_bias_chooses_and_never_weighs():
    logits = jnp.asarray(np.random.default_rng(4).normal(size=(32, 9)), jnp.float32)
    choice, gate = route(logits, jnp.zeros(9))
    assert np.array_equal(np.asarray(choice[:, 0]), np.asarray(logits).argmax(-1))
    forced, forced_gate = route(logits, jnp.zeros(9).at[8].set(100.0))  # every token to the skip
    assert (np.asarray(forced) == 8).all()
    p = np.exp(np.asarray(logits)) / np.exp(np.asarray(logits)).sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(forced_gate[:, 0]), p[:, 8], rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gate[:, 0]), p.max(-1), rtol=1e-5)


def test_the_router_state_crosses_the_layers(bench):
    """The first layer has no EDA weight; every later one reads the state the
    layer before handed on, so a change there moves its routing."""
    params = bench["params"]
    assert "eda" not in params["layers_0"]["router"]
    assert all("eda" in params[f"layers_{i}"]["router"] for i in (1, 2, 3))
    assert params["layers_2"]["router"]["down"].dtype == jnp.float32
    cfg = bench["llm_cfg"]
    h = jnp.asarray(np.random.default_rng(6).normal(size=(1, 32, 64)), jnp.float32)
    mask = jnp.ones((1, 32), bool)
    positions = jnp.arange(32)[None]
    layer = jax.jit(lambda prev: ZayaLayer(cfg).apply(
        {"params": params["layers_1"]}, h, mask, positions, prev))
    _, r_a, _ = layer(jnp.zeros((32, 16)))
    _, r_b, _ = layer(jnp.ones((32, 16)) * 5.0)
    assert not np.allclose(np.asarray(r_a), np.asarray(r_b))


# -- configuration ------------------------------------------------------------


def test_config_reads_the_published_keys_and_refuses_what_it_cannot_build():
    cfg = ZayaConfig.from_hf_dict(PUBLISHED)
    assert cfg == zaya1_8b() and cfg.held == (0, 16) and cfg.holds_every_expert
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim) == (
        2048, 8, 2, 128)
    assert cfg.rotary_dim == 64 and cfg.rope_theta == 5_000_000 and cfg.n_routed_experts == 16
    assert (cfg.router_hidden_size, cfg.moe_intermediate_size, cfg.vocab_size) == (256, 2048, 262272)
    with pytest.raises(ValueError, match="no range"):
        ZayaConfig(experts_held=(10, 20))
    with pytest.raises(ValueError, match="top-1"):
        ZayaConfig(num_experts_per_tok=2)
    with pytest.raises(ValueError, match="kernels of 2"):
        ZayaConfig(cca_time0=4)
    with pytest.raises(ValueError, match="hybrid"):
        ZayaConfig(layer_types=("hybrid_sliding",) * 40)
    with pytest.raises(ValueError, match="rope_parameters"):
        ZayaConfig.from_hf_dict({**PUBLISHED, "rope_parameters": {
            "hybrid": {"partial_rotary_factor": 1.0, "rope_theta": 1e4}}})


def test_preset_and_family_build_it():
    from deepdfa_tpu.llm.families import FAMILIES, build_encoder
    from deepdfa_tpu.llm.presets import PRESETS

    real, small = PRESETS["zaya1_8b_msivd"], PRESETS["tiny_zaya_msivd"]
    assert real.encoder_family == small.encoder_family == "zaya"
    assert real.llm == zaya1_8b(num_hidden_layers=20, experts_held=(0, 16))
    assert real.joint.block_size == 8192 and real.joint.train_batch_size == 2
    assert real.joint.learning_rate == 1e-6 and real.dataset == "precisebugs"
    assert real.joint.train_llm is False and real.joint.use_gnn and not real.joint.freeze_gnn
    assert small.llm == tiny_zaya(vocab_size=2048)
    fam = FAMILIES["zaya"]
    llm, params, _, got = build_encoder(fam, None, 16)
    assert isinstance(llm, ZayaModel) and got == tiny_zaya(vocab_size=2048)
    assert fam.pool == "last" and fam.trained is False and fam.from_checkpoint is None
    assert params["layers_3"]["router"]["mlp_3"].dtype == jnp.float32
    assert params["layers_3"]["router"]["mlp_3"].shape == (16, 9)  # 8 experts and the skip
    # the published widths at the preset's depth, by shape alone: 9.40 GB of leaves, the router's
    # 0.66M a layer float32 (the first layer has no EDA scalar), as are RMSNorm's scales at init
    abstract = nn.meta.unbox(jax.eval_shape(lambda: ZayaModel(real.llm).init(
        jax.random.key(0), jnp.zeros((1, 128), jnp.int32), jnp.ones((1, 128), bool)))["params"])
    size = lambda tree: sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
    assert size(abstract["layers_5"]["router"]) == 4 * (
        2048 * 256 + 2 * 256 * 256 + 256 * 17 + 17 + 1)
    assert round(size(abstract) / 1e9, 2) == 9.40 and round(size(abstract) / 2**30 / 16, 3) == 0.547


def test_the_configuration_file_holds_the_catalog_entry_but_for_the_depth():
    d = json.loads((BENCH / "configs" / "zaya1-8b-msivd.json").read_text())
    assert {k for k, v in PUBLISHED.items() if d.get(k, "absent") != v} == set(d["reduced"]) == {
        "num_hidden_layers"}
    assert d["published"] == {"num_hidden_layers": 40} and d["num_hidden_layers"] == 20
    assert d["experts_held"] == [0, 16] and d["ep_chips"] == 1
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == d["name"])
    assert entry["reduced"] == d["reduced"] and entry["source"] == d["source"]
    assert entry["source"] == "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json"
    assert (d["entry"], d["reference"], d["flops"]) == (
        "joint_trainer_frozen_zaya", "zaya_fusion", "zaya_fusion_train")
    from deepdfa_tpu.llm.presets import PRESETS
    from harness import spec

    drivers = spec.load_module("drivers", d["entry"])
    # the driver's class adds a name to the preset's: the same fields
    assert dataclasses.asdict(drivers.model_config(d)) == dataclasses.asdict(
        PRESETS["zaya1_8b_msivd"].llm)
    assert (d["train"]["block_size"], d["train"]["train_batch_size"]) == (8192, 2)
    assert set(d["limits"]) == set(COMPARED) <= set(d["limit_reasons"])
    assert d["check"]["labels"] == "all_negative"
    # every point the published config leaves open is written down
    assumed = " ".join(d["assumed"])
    for point in ("qk-mean", "value shift", "temperature", "EDA", "router MLP", "17th router output",
                  "softmax over all 17", "no modelling code"):
        assert point in assumed, point


def test_weights_carry_their_logical_axes():
    from deepdfa_tpu.llm.llama import LOGICAL_RULES

    cfg = tiny_zaya(experts_held=(2, 4))
    abstract = jax.eval_shape(lambda: ZayaModel(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), jnp.ones((1, 8), bool)))
    specs = nn.get_partition_spec(abstract)["params"]["layers_1"]
    assert specs["moe"]["experts_gate"][0] == "experts"
    mesh_axes = nn.logical_to_mesh(specs, LOGICAL_RULES)
    P = jax.sharding.PartitionSpec
    assert mesh_axes["attn"]["q_proj"]["kernel"] == P("fsdp", "tp")
    assert mesh_axes["router"]["down"] == P("fsdp", None)
    assert nn.meta.unbox(abstract)["params"]["layers_1"]["moe"]["experts_up"].shape == (2, 64, 32)


# -- planted faults -----------------------------------------------------------


@pytest.fixture(scope="module")
def tiny(bench):
    from harness import traffic

    cfg, reference = bench["cfg"], bench["reference"]
    data = traffic.generate(bench["cell"]["cell"]["traffic"], 5, {"n_examples": 64})
    rows = np.argsort(-data["lengths"], kind="stable")[:12]
    follow = {"step_rows": [rows[:4], rows[4:8], rows[8:]], "total_steps": 100}
    return cfg, reference, data, follow, reference.run(cfg, data, 5, **follow)


def test_reference_against_itself(tiny):
    from harness import compare

    cfg, reference, data, follow, ref = tiny
    again = reference.run(cfg, data, 5, **follow, routing=ref["routing"])
    nums = compare.numbers(reference.COMPARISON, again, ref)
    assert nums["hidden_gap"] == 0 and nums["route_agree_share"] == 1.0
    assert nums["grad1_gap"] == 0 and nums["delta_gap"] == 0
    assert ref["routing"][0].shape[0] == 4  # every layer routes
    w = reference.make_weights(cfg, 5)
    k = np.asarray(w["llm/layers_1/attn/conv_grouped"])
    assert np.array_equal(k, k.astype("bfloat16").astype(np.float32)) and k.std() > 0
    tau = np.asarray(w["llm/layers_1/attn/temperature"])
    assert np.array_equal(tau, tau.astype("bfloat16").astype(np.float32)) and 5 < tau.min()


@pytest.mark.parametrize("control", ["fp8", "half_batch", "state_unchanged", *PARTS])
def test_reference_control_and_faults_read_incorrect(tiny, control):
    """The control and each fault in the program's place, as
    ``tools/prove_frozen.py`` reads them: a fault of the decoder on the first
    checked step, one of the trained part on all three."""
    from harness import compare

    cfg, reference, data, follow, ref = tiny
    assert set(reference.FAULTS) | {"fp8"} >= {control} and len(reference.FAULTS) == 11
    kw = {"precision": "fp8"} if control == "fp8" else {"fault": control}
    if control not in ("half_batch", "state_unchanged"):
        follow = {**follow, "step_rows": follow["step_rows"][:1]}
    other = reference.run(cfg, data, 5, **follow, **kw)
    good = reference.run(cfg, data, 5, **follow, routing=other["routing"])
    nums = compare.numbers(reference.COMPARISON, other, good)
    limits = {k: v for k, v in cfg["limits"].items() if k in nums}  # one forward pass: no tie
    assert set(cfg["limits"]) - set(limits) == {"step_logit_gap", "step_count_gap"}
    assert not compare.judge(nums, limits)[0], nums


# -- the normal path ----------------------------------------------------------


def test_tiny_preset_trains_through_train_joint(tmp_path, monkeypatch):
    monkeypatch.setenv("DEEPDFA_STORAGE", str(tmp_path / "storage"))
    import preprocess
    import train_joint

    preprocess.main(["--dataset", "demo", "--sample", "--workers", "1"])
    out = train_joint.main([
        "--preset", "tiny_zaya_msivd", "--dataset", "demo", "--sample", "--do_train",
        "--block_size", "32", "--output_dir", str(tmp_path / "run")])
    assert out["num_missing"] == 0
    epoch = [h for h in out["history"] if "train_loss" in h]
    assert len(epoch) == 1 and np.isfinite(epoch[0]["train_loss"])
    assert epoch[0]["telemetry"]["steps"] >= 2
    with pytest.raises(SystemExit, match="contradicts preset"):
        train_joint.main(["--preset", "tiny_zaya_msivd", "--encoder", "longcat"])
