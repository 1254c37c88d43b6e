"""Flax LLaMA: parity vs HF transformers (torch CPU), sharding, LoRA, decode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepdfa_tpu.llm.convert import convert_state_dict
from deepdfa_tpu.llm.llama import (
    LOGICAL_RULES,
    LlamaForCausalLM,
    LlamaModel,
    mesh_shardings,
    tiny_llama,
)
from deepdfa_tpu.parallel.mesh import local_mesh

CFG = tiny_llama()


def _hf_model():
    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig as HFConfig
    from transformers import LlamaForCausalLM as HFLlama

    torch.manual_seed(0)
    hf_cfg = HFConfig(
        vocab_size=CFG.vocab_size,
        hidden_size=CFG.hidden_size,
        intermediate_size=CFG.intermediate_size,
        num_hidden_layers=CFG.num_hidden_layers,
        num_attention_heads=CFG.num_attention_heads,
        num_key_value_heads=CFG.num_key_value_heads,
        rope_theta=CFG.rope_theta,
        rms_norm_eps=CFG.rms_norm_eps,
        max_position_embeddings=CFG.max_position_embeddings,
        attn_implementation="eager",
    )
    return HFLlama(hf_cfg).eval()


@pytest.fixture(scope="module")
def hf_and_params():
    hf = _hf_model()
    params = convert_state_dict(hf.state_dict())
    return hf, params


def test_logits_parity_with_hf(hf_and_params):
    import torch

    hf, params = hf_and_params
    ids = np.random.default_rng(1).integers(0, CFG.vocab_size, (2, 12))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    model = LlamaForCausalLM(CFG)
    out = model.apply({"params": params}, jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-4)


def test_left_padded_parity_with_hf(hf_and_params):
    """MSIVD tokenizes with LEFT padding, pad=eos (train.py:196-208); hidden
    states at real positions must match HF under the same attention mask."""
    import torch

    hf, params = hf_and_params
    rng = np.random.default_rng(2)
    ids = rng.integers(3, CFG.vocab_size, (2, 10))
    mask = np.ones((2, 10), dtype=np.int64)
    mask[0, :4] = 0
    mask[1, :2] = 0
    with torch.no_grad():
        ref = hf.model(
            torch.tensor(ids), attention_mask=torch.tensor(mask)
        ).last_hidden_state.numpy()
    bare = convert_state_dict(hf.state_dict(), bare=True)
    out = LlamaModel(CFG).apply(
        {"params": bare}, jnp.asarray(ids), attn_mask=jnp.asarray(mask, bool)
    )
    np.testing.assert_allclose(
        np.asarray(out)[mask.astype(bool)], ref[mask.astype(bool)], atol=2e-4
    )


@pytest.mark.slow
def test_tp_sharded_forward_matches_single(hf_and_params):
    _, params = hf_and_params
    mesh = local_mesh(8, dp=2, tp=4)
    model = LlamaForCausalLM(CFG)
    ids = jnp.asarray(np.random.default_rng(3).integers(0, CFG.vocab_size, (2, 8)))
    ref = model.apply({"params": params}, ids)

    shardings, _ = mesh_shardings(model, mesh, (ids,))
    sharded_params = jax.device_put(
        {"params": params}, shardings
    )
    out = jax.jit(lambda p, i: model.apply(p, i))(sharded_params, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


@pytest.mark.slow
def test_ring_attention_model_matches_full():
    cfg_full = tiny_llama()
    mesh = local_mesh(8, dp=2, sp=4)
    cfg_ring = tiny_llama(attn_impl="ring")
    ids = jnp.asarray(np.random.default_rng(4).integers(0, CFG.vocab_size, (2, 16)))
    model_full = LlamaModel(cfg_full)
    params = model_full.init(jax.random.key(0), ids)["params"]
    ref = model_full.apply({"params": params}, ids)
    out = LlamaModel(cfg_ring, mesh=mesh).apply({"params": params}, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_lora_init_is_noop_and_merge_matches():
    from deepdfa_tpu.llm.lora import lora_mask, merge_lora

    cfg = tiny_llama(lora_rank=4)
    ids = jnp.asarray(np.random.default_rng(5).integers(0, CFG.vocab_size, (2, 8)))
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(0), ids)["params"]
    base_model = LlamaModel(tiny_llama())

    # B=0 init: adapter output must be exactly the base model's
    merged0 = merge_lora(params, alpha=cfg.lora_alpha)
    out_lora = model.apply({"params": params}, ids)
    out_base = base_model.apply({"params": merged0}, ids)
    np.testing.assert_allclose(np.asarray(out_lora), np.asarray(out_base), atol=1e-5)

    # perturb B, merge, compare
    params2 = jax.tree_util.tree_map_with_path(
        lambda p, v: v + 0.01 if any(getattr(k, "key", "") == "lora_b" for k in p) else v,
        params,
    )
    merged = merge_lora(params2, alpha=cfg.lora_alpha)
    out_lora2 = model.apply({"params": params2}, ids)
    out_merged = base_model.apply({"params": merged}, ids)
    np.testing.assert_allclose(
        np.asarray(out_lora2), np.asarray(out_merged), atol=1e-5
    )

    mask = lora_mask(params)
    flat = jax.tree_util.tree_flatten_with_path(mask)[0]
    lora_leaves = [v for p, v in flat if any("lora" in str(k) for k in p)]
    assert lora_leaves and all(lora_leaves)
    other = [v for p, v in flat if not any("lora" in str(k) for k in p)]
    assert other and not any(other)


@pytest.mark.slow
def test_decode_cache_matches_full_forward():
    cfg = tiny_llama(max_position_embeddings=32)
    ids = np.random.default_rng(6).integers(0, cfg.vocab_size, (2, 7))
    model = LlamaForCausalLM(cfg)
    variables = model.init(jax.random.key(0), jnp.asarray(ids))
    params = variables["params"]
    ref = model.apply({"params": params}, jnp.asarray(ids))

    cache = model.init(
        jax.random.key(0), jnp.zeros((2, 1), jnp.int32), decode=True
    )["cache"]
    outs = []
    for t in range(ids.shape[1]):
        step_ids = jnp.asarray(ids[:, t : t + 1])
        pos = jnp.full((2, 1), t, jnp.int32)
        logits, vars_out = model.apply(
            {"params": params, "cache": cache},
            step_ids,
            positions=pos,
            decode=True,
            mutable=["cache"],
        )
        cache = vars_out["cache"]
        outs.append(np.asarray(logits)[:, 0])
    np.testing.assert_allclose(
        np.stack(outs, axis=1), np.asarray(ref), atol=1e-4
    )


@pytest.mark.slow
def test_decode_cache_respects_left_padding():
    """Padded prompt tokens must never contribute to the cache attention:
    decoding a left-padded batch must match the full forward with the same
    attention mask at every real position."""
    cfg = tiny_llama(max_position_embeddings=32)
    rng = np.random.default_rng(7)
    ids = rng.integers(3, cfg.vocab_size, (2, 8))
    mask = np.ones((2, 8), dtype=bool)
    mask[0, :3] = False  # row 0: 3 left-pad positions
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.key(0), jnp.asarray(ids))["params"]
    ref = model.apply(
        {"params": params}, jnp.asarray(ids), attn_mask=jnp.asarray(mask)
    )

    cache = model.init(
        jax.random.key(0), jnp.zeros((2, 1), jnp.int32), decode=True
    )["cache"]
    outs = []
    for t in range(ids.shape[1]):
        logits, vars_out = model.apply(
            {"params": params, "cache": cache},
            jnp.asarray(ids[:, t : t + 1]),
            attn_mask=jnp.asarray(mask[:, t : t + 1]),
            positions=jnp.full((2, 1), t, jnp.int32),
            decode=True,
            mutable=["cache"],
        )
        cache = vars_out["cache"]
        outs.append(np.asarray(logits)[:, 0])
    got = np.stack(outs, axis=1)
    np.testing.assert_allclose(got[mask], np.asarray(ref)[mask], atol=1e-4)
