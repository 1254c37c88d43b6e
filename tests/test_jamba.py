"""The hybrid state-space / attention decoder (``llm/jamba.py``,
``ops/selective_scan.py``) against the plain reference
(``benchmark/reference/jamba_fusion.py``) and a float64 loop on seeded weights
at a tiny size: hidden states in float32 and bfloat16, every scan form and the
convolution, **left-padding exactness** (a Mamba layer alone, an attention
layer alone, the stack), causality, the layer pattern and the parameter count
at the published sizes, the counts on ``loss.sync`` through
``JointTrainer.train`` with the decoder frozen, the reference's control and
faults, every fault planted in the program reading ``correct: false``, the tiny
preset through ``scripts/train_joint.py``."""

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

from deepdfa_tpu.llm.jamba import (
    JambaConfig,
    JambaLayer,
    JambaModel,
    dt_bias_init,
    jamba2_3b,
    tiny_jamba,
)
from deepdfa_tpu.ops.selective_scan import UNROLL, causal_conv1d, selective_scan

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "benchmark"
for p in (str(BENCH), str(BENCH / "tools"), str(ROOT / "scripts")):
    if p not in sys.path:
        sys.path.insert(0, p)
TINY_BENCH = BENCH / "tests" / "BENCHMARK.jamba.tiny.json"
CELL = "tiny-jamba2-3b-msivd.joint"
COMPARED = ("grad1_gap", "delta_gap", "hidden_gap", "step_logit_gap", "step_count_gap")
PADS = (1, 3, 17)
# the catalog row's ``config`` (model-configs/architectures.jsonl, AI21-Jamba2-3B)
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
    "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
    "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba", "num_attention_heads": 20,
    "num_experts": 1, "num_experts_per_tok": 1, "num_hidden_layers": 28,
    "num_key_value_heads": 1, "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
    "sliding_window": None, "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536}


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
                llm_cfg=llm_cfg, model=JambaModel(llm_cfg), params=params, data=data)


def _gap(got, want, mask):
    got, want = np.asarray(got, np.float64)[mask], np.asarray(want, np.float64)[mask]
    return (np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)).max()


# -- the program against the plain reference ---------------------------------


@pytest.mark.parametrize("dtype,limit", [
    # the same arithmetic in another order: rounding alone (read 1.4e-6)
    ("float32", 1e-5),
    # bfloat16 weights are the reference's own (bfloat16-representable); activations rounded
    # to 8 bits of mantissa after each of 8 layers' sub-layers: 2^-8 x sqrt(16 roundings)
    # ~ 0.016 a token; read: mean token 0.0144-0.0148, the worst of 165 tokens 0.024-0.026
    # (seeds 7, 8, 9). The float32 program's faults read 0.017 (the weakest) to 1.4
    ("bfloat16", 0.05),
])
def test_hidden_states_match_the_reference(bench, dtype, limit):
    rows = np.arange(4)
    ids, mask = bench["data"]["input_ids"][rows], bench["data"]["pad_mask"][rows]
    assert not mask.all() and mask.any(1).all()  # left-padded rows, none empty
    model = JambaModel(dataclasses.replace(bench["llm_cfg"], dtype=dtype))
    keep = bench["reference"].FLOAT32_LEAVES
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x if path[-1].key in keep else x.astype(dtype), bench["params"])
    hidden, sown = model.apply({"params": params}, ids, mask, mutable=["stats"])
    assert hidden.dtype == jnp.dtype(dtype)
    ref_h = bench["reference"].decoder(bench["cfg"], bench["w"], ids, mask)
    gap = _gap(hidden, ref_h, mask)
    assert gap < limit, gap
    assert gap > 1e-4 or dtype == "float32"  # the bfloat16 case did compute in bfloat16
    stats = jax.device_get(sown["stats"])
    real, dense = int(mask.sum()), int(mask.any(1).sum()) * 64  # whole rows: 512 does not tile 64
    assert stats == {
        "ssm": {"layers": 6, "fused": 0, "tokens_real": 6 * real, "tokens_dense": 6 * dense},
        "attn": {"layers": 2, "fused": 0, "tokens_real": 2 * real, "tokens_dense": 2 * dense}}


def _scan_inputs(seed=0, b=2, s=64, d=24, n=16):
    rng = np.random.default_rng(seed)
    mask = np.arange(s)[None, :] >= np.array([[5], [17]])
    f = lambda *shape: rng.normal(size=shape).astype(np.float32)
    c = f(b, s, d) * mask[..., None]
    delta = np.log1p(np.exp(f(b, s, d) - 3.0))
    a = -np.exp(f(d, n))
    return c, delta, a, f(b, s, n), f(b, s, n), f(d), mask


def _scan_loop(c, delta, a, b_in, c_in, d_skip, mask):
    """The recurrence position by position in float64."""
    c, delta, a = (v.astype(np.float64) for v in (c, delta, a))
    y = np.zeros_like(c)
    for i in range(c.shape[0]):
        state = np.zeros(a.shape)
        for t in range(c.shape[1]):
            x = delta[i, t] * c[i, t] * mask[i, t]
            state = np.exp(delta[i, t][:, None] * a) * state + x[:, None] * b_in[i, t][None]
            y[i, t] = state @ c_in[i, t] + d_skip * c[i, t]
    return y


@pytest.mark.parametrize("s", [
    2 * UNROLL,      # whole trips of the loop
    UNROLL + 8,      # a trip and a remainder
    UNROLL - 11])    # less than a trip
def test_selective_scan_matches_a_float64_loop(s):
    args = _scan_inputs(s=s)
    want = _scan_loop(*args)
    got = np.asarray(selective_scan(*args))
    assert np.abs(got - want).max() / np.abs(want).max() < 2e-6  # float32 state: read 2e-7 - 4e-7
    # a pad adds nothing to the state even where ``c`` comes in unmasked
    noisy = (args[0] + np.float32(7.0) * ~args[-1][..., None], *args[1:])
    again = np.asarray(selective_scan(*noisy))
    assert np.array_equal(again[args[-1]], got[args[-1]])
    # no mask: every position real (``c`` is 0 at the pads of ``args`` already)
    assert np.allclose(np.asarray(selective_scan(*args[:-1])), got, atol=1e-6)


def test_causal_conv1d_matches_a_float64_loop():
    rng = np.random.default_rng(1)
    b, s, d, k = 2, 40, 12, 4
    u = rng.normal(size=(b, s, d)).astype(np.float32)
    w, bias = rng.normal(size=(k, d)).astype(np.float32), rng.normal(size=(d,)).astype(np.float32)
    mask = np.arange(s)[None, :] >= np.array([[0], [9]])
    want = np.zeros((b, s, d))
    for i in range(b):
        for t in range(s):
            acc = bias.astype(np.float64)
            for j in range(k):  # w[j] on u_{t-(k-1)+j}; before position 0, and at a pad, 0
                src = t - (k - 1) + j
                if src >= 0 and mask[i, src]:
                    acc = acc + w[j] * u[i, src]
            want[i, t] = acc / (1.0 + np.exp(-acc)) * mask[i, t]
    got = np.asarray(causal_conv1d(u, w, bias, mask))
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert np.all(got[1, :9] == 0)
    # no mask: every position real
    np.testing.assert_allclose(np.asarray(causal_conv1d(u, w, bias))[0], want[0], atol=2e-6)


# -- padding and causality -----------------------------------------------------


def _one_layer(bench, attention):
    cfg = bench["llm_cfg"]
    i = cfg.attention_layers[0] if attention else 0
    return JambaLayer(cfg, attention), bench["params"][f"layers_{i}"]


def _padded_runs(apply, body, pad):
    """``apply(x [1, s, ...], mask)`` over ``body`` alone and left-padded by
    ``pad`` positions of junk: the real positions of both."""
    s = body.shape[0]
    junk = np.random.default_rng(pad).normal(size=(pad, *body.shape[1:])).astype(body.dtype) \
        if body.dtype.kind == "f" else np.full((pad,), 77, body.dtype)
    alone = apply(body[None], np.ones((1, s), bool))
    padded = apply(np.concatenate([junk, body])[None], (np.arange(pad + s) >= pad)[None])
    return np.asarray(alone)[0], np.asarray(padded)[0, pad:]


@pytest.mark.parametrize("pad", PADS)
@pytest.mark.parametrize("kind", ["mamba", "attention", "stack"])
def test_left_padding_leaves_the_real_tokens_states_as_they_were(bench, kind, pad):
    """A row padded on the left reads what the row alone would: the state is 0
    at the first real token, exactly, and an attention layer masks pads as
    keys. To rounding (1e-6 relative, float32): the products' blocks shift
    with the padding."""
    rng = np.random.default_rng(3)
    if kind == "stack":
        body = rng.integers(3, 320, size=23).astype(np.int32)
        apply = lambda x, m: bench["model"].apply({"params": bench["params"]}, x, m)
    else:
        layer, p = _one_layer(bench, kind == "attention")
        body = rng.normal(size=(23, 64)).astype(np.float32)
        apply = lambda x, m: layer.apply({"params": p}, x, m)
    alone, padded = _padded_runs(apply, body, pad)
    gap = np.linalg.norm(padded - alone, axis=-1) / np.linalg.norm(alone, axis=-1)
    assert gap.max() <= 1e-6, gap.max()


@pytest.mark.parametrize("kind", ["mamba", "attention", "stack"])
def test_a_later_token_changes_no_earlier_state(bench, kind):
    rng = np.random.default_rng(4)
    t = 11
    if kind == "stack":
        x = rng.integers(3, 320, size=(1, 24)).astype(np.int32)
        other = x.copy()
        other[0, t] = (x[0, t] + 5) % 317 + 3
        apply = lambda v: bench["model"].apply({"params": bench["params"]}, v, np.ones((1, 24), bool))
    else:
        layer, p = _one_layer(bench, kind == "attention")
        x = rng.normal(size=(1, 24, 64)).astype(np.float32)
        other = x.copy()
        other[0, t] += 1.0
        apply = lambda v: layer.apply({"params": p}, v, np.ones((1, 24), bool))
    a, b = np.asarray(apply(x))[0], np.asarray(apply(other))[0]
    assert np.array_equal(a[:t], b[:t])
    assert not np.allclose(a[t], b[t]) and not np.allclose(a[-1], b[-1])  # and does reach the later ones


# -- the chunked halves ------------------------------------------------------------

CHUNK_PADS = ((0, 5, 16, 63), (64, 17, 40, 0))  # left pads a row at the block of 64: one row
# wholly real, one wholly pad, a chunk's edge, a chunk's middle


def _chunked_against_whole(cfg, params, ids, mask):
    """Each layer of the stack, chunked and over the whole block, on the
    whole block's states of the layer before: ``[(chunked, whole)]``."""
    x = jnp.take(params["embed_tokens"]["embedding"], ids, axis=0)
    out = []
    for i in range(cfg.num_hidden_layers):
        layer, p = JambaLayer(cfg, cfg.is_attention(i)), {"params": params[f"layers_{i}"]}
        chunked = layer.apply(p, x, mask)
        x = layer.apply(p, x, mask, method=JambaLayer.whole)
        out.append((np.asarray(chunked), np.asarray(x)))
    return out


@pytest.mark.parametrize("pads", CHUNK_PADS)
def test_the_chunked_halves_are_the_whole_blocks(bench, pads, monkeypatch):
    """Chunks of 16 positions at the block of 64, the plain scan: every
    layer's real tokens are the whole-block layer's, bit for bit in float32
    (a chunk's products are the same rows' products)."""
    from deepdfa_tpu.llm import jamba

    monkeypatch.setattr(jamba, "DENSE_BLOCK", 16)
    ids = jnp.asarray(bench["data"]["input_ids"][:len(pads)])
    mask = np.arange(64)[None] >= np.asarray(pads)[:, None]
    for i, (chunked, whole) in enumerate(_chunked_against_whole(
            bench["llm_cfg"], bench["params"], ids, jnp.asarray(mask))):
        assert np.array_equal(chunked[mask], whole[mask]), i


def test_the_kernel_and_the_chunked_halves_are_the_whole_blocks(monkeypatch):
    """The same at the widths the scan's kernel takes (1,024 channels, 16
    states), the kernel under the Pallas interpreter: chunks of 16 at the
    block of 48, two Mamba layers and one attention layer. To float32
    rounding, not bit for bit: the CPU multiplies 512-wide rows in another
    order for 16 rows than for 144 (read: 1.9e-6 a token at worst)."""
    from deepdfa_tpu.llm import jamba
    from deepdfa_tpu.ops import dispatch

    cfg = tiny_jamba(hidden_size=512, num_hidden_layers=3, mamba_d_state=16, mamba_dt_rank=16)
    ids = jnp.asarray(np.random.default_rng(0).integers(3, cfg.vocab_size, (3, 48)))
    mask = np.arange(48)[None] >= np.array([[0], [19], [32]])
    params = nn.meta.unbox(JambaModel(cfg).init(jax.random.key(0), ids, jnp.asarray(mask)))
    monkeypatch.setattr(dispatch, "device_mode", lambda: True)
    monkeypatch.setattr(jamba, "DENSE_BLOCK", 16)
    assert jamba._fused_scan(cfg, 48) is True
    for i, (chunked, whole) in enumerate(_chunked_against_whole(
            cfg, params["params"], ids, jnp.asarray(mask))):
        assert _gap(chunked, whole, mask) < 5e-6, i


@pytest.mark.parametrize("attention", [False, True])
def test_a_chunk_of_pads_leaves_the_layers_input_as_it_was(bench, attention, monkeypatch):
    """Where a chunk holds no real token the layer hands its input on: ``post``
    is skipped there, and whatever ``pre`` would have made is never read."""
    from deepdfa_tpu.llm import jamba

    monkeypatch.setattr(jamba, "DENSE_BLOCK", 16)
    layer, p = _one_layer(bench, attention)
    x = np.random.default_rng(5).normal(size=(2, 64, 64)).astype(np.float32)
    mask = np.arange(64)[None] >= np.array([[40], [0]])
    out = np.asarray(layer.apply({"params": p}, x, mask))
    assert np.array_equal(out[0, :32], x[0, :32])  # row 0's two leading chunks: pads alone
    assert not np.allclose(out[0, 32:40], x[0, 32:40])  # a live chunk's pads are computed
    assert not np.allclose(out[1], x[1])


# the tree ``init`` draws for ``tiny_jamba`` at key 0, as the whole-block layers drew it:
# shape and the sum of |leaf| (float64) of the first Mamba and the first attention layer
TINY_TREE = {
    "embed_tokens/embedding": ((320, 64), 325.0097708759616),
    "layers_0/ffn_norm/weight": ((64,), 64.0),
    "layers_0/input_norm/weight": ((64,), 64.0),
    "layers_0/mamba/A_log": ((128, 8), 1357.3891906738281),
    "layers_0/mamba/D": ((128,), 128.0),
    "layers_0/mamba/b_norm/weight": ((8,), 8.0),
    "layers_0/mamba/c_norm/weight": ((8,), 8.0),
    "layers_0/mamba/conv_bias": ((128,), 0.0),
    "layers_0/mamba/conv_kernel": ((4, 128), 209.25795178834233),
    "layers_0/mamba/dt_bias": ((128,), 609.1960985660553),
    "layers_0/mamba/dt_norm/weight": ((8,), 8.0),
    "layers_0/mamba/dt_proj/kernel": ((8, 128), 295.32936238746333),
    "layers_0/mamba/in_proj/kernel": ((64, 256), 1681.1223501330014),
    "layers_0/mamba/out_proj/kernel": ((128, 64), 589.3698186514939),
    "layers_0/mamba/x_proj/kernel": ((128, 24), 222.27097380716327),
    "layers_0/mlp/down_proj/kernel": ((128, 64), 591.1019345631685),
    "layers_0/mlp/gate_proj/kernel": ((64, 128), 828.0084562472442),
    "layers_0/mlp/up_proj/kernel": ((64, 128), 839.8800835712937),
    "layers_2/attn/k_proj/kernel": ((64, 16), 108.47727585904067),
    "layers_2/attn/o_proj/kernel": ((64, 64), 420.76580575139815),
    "layers_2/attn/q_proj/kernel": ((64, 64), 410.5947795348138),
    "layers_2/attn/v_proj/kernel": ((64, 16), 104.83856603857566),
    "layers_2/ffn_norm/weight": ((64,), 64.0),
    "layers_2/input_norm/weight": ((64,), 64.0),
    "layers_2/mlp/down_proj/kernel": ((128, 64), 594.4988215171029),
    "layers_2/mlp/gate_proj/kernel": ((64, 128), 843.5672124120247),
    "layers_2/mlp/up_proj/kernel": ((64, 128), 841.765269592217),
    "norm/weight": ((64,), 64.0),
}


def test_init_draws_the_leaves_it_always_drew():
    from flax.traverse_util import flatten_dict

    cfg = tiny_jamba()
    ids = jnp.zeros((1, 32), jnp.int32)
    leaves = flatten_dict(nn.meta.unbox(JambaModel(cfg).init(
        jax.random.key(0), ids, ids == 0)["params"]), sep="/")
    kind = lambda i: "layers_2/" if cfg.is_attention(i) else "layers_0/"
    assert {n: x.shape for n, x in leaves.items()} == {
        n.replace(kind(i), f"layers_{i}/"): shape for n, (shape, _) in TINY_TREE.items()
        for i in range(cfg.num_hidden_layers) if not n.startswith("layers_") or n.startswith(kind(i))}
    for n, (_, total) in TINY_TREE.items():
        assert np.abs(np.asarray(leaves[n], np.float64)).sum() == pytest.approx(total, rel=1e-6), n


@pytest.mark.parametrize("block,live", [
    (16, 4 + 3 + 0 + 1),  # chunks of 16 at the block of 64: the live chunks of each row
    (512, 3),             # 512 does not tile 64: whole rows, the three that hold a real token
])
def test_the_dense_tokens_are_the_live_chunks_times_their_width(bench, block, live, monkeypatch):
    from deepdfa_tpu.llm import jamba

    monkeypatch.setattr(jamba, "DENSE_BLOCK", block)
    mask = np.arange(64)[None] >= np.array([[0], [20], [64], [50]])
    ids = jnp.asarray(bench["data"]["input_ids"][:4])
    _, sown = bench["model"].apply({"params": bench["params"]}, ids, mask, mutable=["stats"])
    width = min(block, 64)
    stats = jax.device_get(sown["stats"])
    for name, layers in (("ssm", 6), ("attn", 2)):
        assert stats[name] == {"layers": layers, "fused": 0, "tokens_real": layers * (64 + 44 + 14),
                               "tokens_dense": layers * live * width}


# -- the published sizes --------------------------------------------------------


def _abstract_params(cfg):
    shapes = jax.eval_shape(lambda: JambaModel(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), jnp.ones((1, 8), bool)))
    return nn.meta.unbox(shapes["params"])


def test_the_layer_pattern_puts_attention_at_layers_7_and_21_and_an_mlp_everywhere():
    cfg = JambaConfig.from_hf_dict(PUBLISHED)
    assert cfg == jamba2_3b() and cfg.attention_layers == (7, 21)
    assert (cfg.head_dim, cfg.d_inner) == (128, 5120)
    p = _abstract_params(cfg)
    assert set(p) == {"embed_tokens", "norm", *(f"layers_{i}" for i in range(28))}
    for i in range(28):
        layer = p[f"layers_{i}"]
        mixer = "attn" if i in (7, 21) else "mamba"
        assert set(layer) == {"input_norm", mixer, "ffn_norm", "mlp"}, i
        assert layer["mlp"]["gate_proj"]["kernel"].shape == (2560, 8192)
    attn, mamba = p["layers_7"]["attn"], p["layers_0"]["mamba"]
    assert attn["q_proj"]["kernel"].shape == (2560, 20 * 128)
    assert attn["k_proj"]["kernel"].shape == attn["v_proj"]["kernel"].shape == (2560, 128)
    assert set(mamba) == {"in_proj", "conv_kernel", "conv_bias", "x_proj", "dt_norm", "b_norm",
                          "c_norm", "dt_proj", "dt_bias", "A_log", "D", "out_proj"}
    assert mamba["x_proj"]["kernel"].shape == (5120, 160 + 16 + 16)
    assert mamba["A_log"].shape == (5120, 16) and mamba["conv_kernel"].shape == (4, 5120)
    # Mamba's convention: these three stay float32 in a bfloat16 model
    assert {k for k, v in mamba.items() if hasattr(v, "dtype") and v.dtype == jnp.float32} == {
        "A_log", "D", "dt_bias"}


def test_the_published_model_counts_3_029_337_472_parameters():
    leaves = jax.tree.leaves(_abstract_params(jamba2_3b()))
    assert sum(int(np.prod(x.shape)) for x in leaves) == 3_029_337_472
    mixer = 2560 * 10240 + (5120 * 4 + 5120) + 5120 * 192 + (160 * 5120 + 5120) + 5120 * 16 \
        + 5120 + 192 + 5120 * 2560
    mamba_layer, attn_layer = mixer + 3 * 2560 * 8192 + 5120, 13_762_560 + 3 * 2560 * 8192 + 5120
    assert (mixer, mamba_layer, attn_layer) == (41_241_792, 104_161_472, 76_682_240)
    assert 26 * mamba_layer + 2 * attn_layer + 65536 * 2560 + 2560 == 3_029_337_472
    d = json.loads((BENCH / "configs" / "jamba2-3b-msivd.json").read_text())
    assert "3,029,337,472 parameters = 6.06 GB" in d["bytes"]["frozen"]


def test_the_routed_variant_and_other_layers_are_refused():
    with pytest.raises(ValueError, match="routed variant"):
        JambaConfig(num_experts=16, num_experts_per_tok=2)
    with pytest.raises(ValueError, match="another layer"):
        JambaConfig(mamba_proj_bias=True)
    with pytest.raises(ValueError, match="another layer"):
        JambaConfig(mamba_conv_bias=False)
    with pytest.raises(ValueError, match="heads"):
        JambaConfig(num_attention_heads=24)


def test_the_stats_count_26_scans_and_2_attention_layers_at_the_published_depth():
    cfg = tiny_jamba(num_hidden_layers=28, attn_layer_period=14, attn_layer_offset=7)
    model = JambaModel(cfg)
    ids, mask = jnp.ones((1, 8), jnp.int32), jnp.ones((1, 8), bool)
    params = model.init(jax.random.key(0), ids, mask)["params"]
    plain = model.apply({"params": params}, ids, mask)
    assert isinstance(plain, jax.Array)  # an apply without mutable=["stats"] sows nothing
    _, sown = model.apply({"params": params}, ids, mask, mutable=["stats"])
    assert jax.device_get(sown["stats"]) == {
        "ssm": {"layers": 26, "fused": 0, "tokens_real": 26 * 8, "tokens_dense": 26 * 8},
        "attn": {"layers": 2, "fused": 0, "tokens_real": 2 * 8, "tokens_dense": 2 * 8}}


def test_seeded_weights_start_where_mamba_1_starts():
    p = nn.meta.unbox(JambaModel(tiny_jamba()).init(
        jax.random.key(0), jnp.ones((1, 8), jnp.int32), jnp.ones((1, 8), bool))["params"])
    m = p["layers_0"]["mamba"]
    np.testing.assert_allclose(np.exp(m["A_log"]), np.tile(np.arange(1, 9.0), (128, 1)), rtol=1e-6)
    assert np.all(np.asarray(m["D"]) == 1)
    dt = np.asarray(jax.nn.softplus(m["dt_bias"]))
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001
    wide = np.asarray(jax.nn.softplus(dt_bias_init(jax.random.key(1), (4096,))))
    assert wide.min() < 2e-3 and wide.max() > 5e-2  # log-uniform over the two decades


def test_weights_carry_their_logical_axes():
    from deepdfa_tpu.llm.llama import LOGICAL_RULES

    abstract = jax.eval_shape(lambda: JambaModel(tiny_jamba()).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), jnp.ones((1, 8), bool)))
    specs = nn.get_partition_spec(abstract)["params"]
    mesh = nn.logical_to_mesh(specs, LOGICAL_RULES)
    P = jax.sharding.PartitionSpec
    mamba, attn = mesh["layers_0"]["mamba"], mesh["layers_2"]["attn"]
    assert mamba["in_proj"]["kernel"] == P("fsdp", "tp") and mamba["out_proj"]["kernel"] == P("tp", "fsdp")
    assert mamba["conv_kernel"] == P(None, "tp") and mamba["A_log"] == P("tp", None)
    assert mamba["dt_bias"] == mamba["D"] == P("tp")  # the channels go with the inner width
    assert attn["q_proj"]["kernel"] == P("fsdp", "tp") and attn["k_proj"]["kernel"] == P("fsdp", "tp")


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
    assert driver.jcfg.train_llm is False and isinstance(driver.trainer.llm, JambaModel)
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


@pytest.mark.parametrize("number,limit", [
    ("loss1_gap", 1e-5), ("loss3_gap", 1e-5), ("grad1_gap", 1e-4), ("delta_gap", 1e-3),
    ("hidden_gap", 1e-4), ("pooled_gap", 1e-4), ("logit_gap", 1e-4),
])
def test_train_steps_match_the_reference_closely(followed, number, limit):
    assert followed["nums"][number] <= limit


def test_the_mixer_counts_are_on_the_loss_sync_spans(bench, followed):
    t0, t1 = followed["ran"]
    spans = [s for s in followed["driver"].trainer.telemetry.tracer.spans()
             if s.name == "loss.sync" and "ssm_layers" in s.attrs and t0 <= s.start_s <= t1]
    assert len(spans) >= followed["driver"].setup_steps - 1  # the step in flight is not read
    block = bench["cfg"]["train"]["block_size"]  # 512 does not tile it: whole rows
    for s in spans:
        counts = {k: v for k, v in s.attrs.items() if k.startswith(("ssm_", "attn_"))}
        real, dense = counts.pop("ssm_tokens_real"), counts.pop("ssm_tokens_dense")
        assert counts.pop("attn_tokens_real") * 6 == real * 2 and real % 6 == 0 and real > 0
        assert counts.pop("attn_tokens_dense") * 6 == dense * 2 and dense % (6 * block) == 0
        assert real <= dense <= 6 * 4 * block
        assert counts == {"ssm_layers": 6, "ssm_fused": 0, "attn_layers": 2, "attn_fused": 0}
        assert not any(k.startswith("moe_") for k in s.attrs)  # nothing is routed
    tie = followed["run"]["readings"]["tie"]
    assert tie["counts"] == tie["step_counts"] and len(tie["counts"]) == 3
    assert all(len(c) == 8 for c in tie["counts"])  # the four counts of each kind, on both sides


# -- the reference's control and faults, and faults planted in the program -------


@pytest.fixture(scope="module")
def tiny(bench):
    from harness import traffic

    cfg, reference = bench["cfg"], bench["reference"]
    data = traffic.generate(bench["cell"]["cell"]["traffic"], 5, {"n_examples": 64})
    follow = {"step_rows": [np.arange(4), np.arange(4, 8), np.arange(8, 12)], "total_steps": 100}
    return cfg, reference, data, follow, reference.run(cfg, data, 5, **follow)


def test_reference_against_itself_and_its_weights(tiny):
    from harness import compare

    cfg, reference, data, follow, ref = tiny
    nums = compare.numbers(reference.COMPARISON, reference.run(cfg, data, 5, **follow), ref)
    assert nums["hidden_gap"] == 0 and nums["grad1_gap"] == 0 and nums["delta_gap"] == 0
    w = reference.make_weights(cfg, 5)
    k = np.asarray(w["llm/layers_0/mamba/in_proj/kernel"])
    assert np.array_equal(k, k.astype("bfloat16").astype(np.float32)) and k.std() > 0
    np.testing.assert_allclose(np.exp(w["llm/layers_0/mamba/A_log"])[3], np.arange(1, 9.0), rtol=1e-6)
    assert np.all(np.asarray(w["llm/layers_1/mamba/D"]) == 1)
    dt = np.asarray(jax.nn.softplus(w["llm/layers_1/mamba/dt_bias"]))
    assert 1e-3 * 0.999 <= dt.min() < dt.max() <= 1e-1 * 1.001
    assert not np.array_equal(w["llm/layers_0/mamba/dt_bias"], w["llm/layers_1/mamba/dt_bias"])
    assert "llm/layers_2/attn/k_proj/kernel" in w and "llm/layers_2/mamba/in_proj/kernel" not in w
    assert w["llm/layers_2/attn/k_proj/kernel"].shape == (64, 16)  # one key/value head


@pytest.mark.parametrize("control", [
    "fp8", "half_batch", "state_unchanged", "mask_before_conv_skipped",
    "mask_after_conv_skipped", "state_bf16", "d_skip_skipped", "inner_norm_skipped",
    "attention_as_mamba", "rope_in_attention", "taps_reversed"])
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
    sys.path.insert(0, str(BENCH))
    import run

    assert run.main(["--workload", CELL, "--seed", "11", "--seconds", "0.3", "--trace", "0",
                     "--benchmark-file", str(TINY_BENCH)]) == 0
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return row, {k for k, v in row["compared"].items() if not v["value"] <= v["limit"]}


@pytest.mark.parametrize("kind", [
    "mask_before_conv_skipped", "mask_after_conv_skipped", "state_bf16", "d_skip_skipped",
    "inner_norm_skipped", "attention_as_mamba", "rope_in_attention", "taps_reversed"])
def test_correct_is_false_with_the_fault_planted_in_the_program(kind, monkeypatch, capsys):
    import prove_frozen_jamba

    prove_frozen_jamba.plant(kind, monkeypatch.setattr)
    row, over = _last_row(capsys)
    assert row["correct"] is False and "hidden_gap" in over, row["compared"]
    assert not over & {"step_logit_gap", "step_count_gap"}  # the check's pass is planted alike


@pytest.mark.parametrize("kind,number", [("d_skip_skipped", "step_logit_gap"),
                                         ("count_off", "step_count_gap")])
def test_correct_is_false_with_a_fault_in_the_timed_step_alone(kind, number, monkeypatch, capsys):
    """The check's own pass stays good, so the number that reads it passes;
    what ties it to the timed step does not."""
    import prove_frozen_jamba
    from harness import spec

    drivers = spec.load_module("drivers", "joint_trainer_frozen_jamba")
    real_load = drivers.Driver.load

    def load(self, *a):
        real_load(self, *a)
        prove_frozen_jamba.step_alone(self, kind)

    monkeypatch.setattr(drivers.Driver, "load", load)
    row, over = _last_row(capsys)
    assert row["correct"] is False and number in over, row["compared"]
    assert "hidden_gap" not in over, row["compared"]


# -- the normal path ----------------------------------------------------------


def test_tiny_preset_trains_through_train_joint(tmp_path, monkeypatch):
    monkeypatch.setenv("DEEPDFA_STORAGE", str(tmp_path / "storage"))
    import preprocess
    import train_joint

    preprocess.main(["--dataset", "demo", "--sample", "--workers", "1"])
    out = train_joint.main([
        "--preset", "tiny_jamba_msivd", "--dataset", "demo", "--sample", "--do_train",
        "--block_size", "32", "--output_dir", str(tmp_path / "run")])
    assert out["num_missing"] == 0
    epoch = [h for h in out["history"] if "train_loss" in h]
    assert len(epoch) == 1 and np.isfinite(epoch[0]["train_loss"])
    assert epoch[0]["telemetry"]["steps"] >= 2
    with pytest.raises(SystemExit, match="contradicts preset"):
        train_joint.main(["--preset", "tiny_jamba_msivd", "--encoder", "longcat"])


def test_the_presets_are_the_published_model_uncut_and_its_tiny_twin():
    from deepdfa_tpu.llm.families import FAMILIES, build_encoder
    from deepdfa_tpu.llm.presets import PRESETS

    real, small = PRESETS["jamba2_3b_msivd"], PRESETS["tiny_jamba_msivd"]
    assert real.encoder_family == small.encoder_family == "jamba"
    assert real.llm == jamba2_3b() == JambaConfig.from_hf_dict(PUBLISHED)  # nothing cut
    assert real.joint.block_size == 2048 and real.joint.train_batch_size == 4
    assert real.joint.learning_rate == 1e-6 and real.dataset == "precisebugs" and not real.finetuned
    assert real.joint.train_llm is False and real.joint.use_gnn and not real.joint.freeze_gnn
    assert small.llm == tiny_jamba(vocab_size=2048) and len(small.llm.attention_layers) == 2
    fam = FAMILIES["jamba"]
    llm, params, _, cfg = build_encoder(fam, None, 16)
    assert isinstance(llm, JambaModel) and cfg == tiny_jamba(vocab_size=2048)
    assert fam.pool == "last" and fam.trained is False and fam.from_checkpoint is None
    assert not any(isinstance(x, nn.Partitioned) for x in jax.tree.leaves(
        params, is_leaf=lambda x: isinstance(x, nn.Partitioned)))


def test_the_configuration_file_holds_the_catalog_entry_uncut():
    d = json.loads((BENCH / "configs" / "jamba2-3b-msivd.json").read_text())
    assert {k: d.get(k, "absent") for k in PUBLISHED} == PUBLISHED and d["reduced"] == []
    assert d["published"] == {k: PUBLISHED[k] for k in d["published"]}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == d["name"])
    assert entry["reduced"] == [] and entry["source"] == d["source"]
    assert entry["source"] == "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json"
    assert (d["entry"], d["reference"], d["flops"]) == (
        "joint_trainer_frozen_jamba", "jamba_fusion", "jamba_fusion_train")
    from harness import spec

    drivers = spec.load_module("drivers", d["entry"])
    assert drivers.model_config(d) == jamba2_3b()
    assert set(d["limits"]) == set(COMPARED) <= set(d["limit_reasons"])
    assert d["check"]["labels"] == "all_negative" and d["head"] == {"pool": "last", "dropout_rate": 0.0}
