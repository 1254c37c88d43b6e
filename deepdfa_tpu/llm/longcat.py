"""A latent-attention, routed-expert decoder (the LongCat-Flash layer) in Flax.

One of the four encoder stacks (``llama.py``: causal, dense; ``roberta.py``:
bidirectional; this file and ``pangu_moe.py``: causal, latent attention and
routed experts): a causal decoder whose layer holds **two**
latent-attention blocks and two dense gated FFNs, with one routed-expert
layer on a *shortcut* — it reads the first block's output and joins after the
second dense FFN — and whose router may send a token to *zero-compute*
experts that return their input. Config keys are the published
``config.json``'s (``LongcatConfig.from_hf_dict`` reads one directly).

Layer input ``h`` [tokens, hidden]; RMSNorm, no biases::

    for i in (0, 1):
        a = h + MLA_i(RMSNorm(h))
        u = RMSNorm(a)
        if i == 0: s = MoE(u)
        h = a + W_down_i(silu(W_gate_i u) * (W_up_i u))
        if i == 1: h = h + s
    MLA(x): c_q = RMSNorm(W_qa x) * sqrt(hidden / q_lora_rank)
            q = W_qb c_q -> heads x (nope | rope)
            [c_kv | k_r] = W_kva x ; c_kv = RMSNorm(c_kv) * sqrt(hidden / kv_lora_rank)
            [k_n | v] = W_kvb c_kv -> heads x (nope | v) ; k_r shared by all heads
            RoPE on q's rope part and on k_r (interleaved pairs)
            scores = (q_n.k_n + q_r.k_r) / sqrt(nope + rope), causal, pad-masked
    MoE(u): p = softmax(W_r u) over routed + zero experts, float32
            choice = top-k of (p + b) ; g = scaling * p[choice]   (not renormalised)
            E_e(u) = gated FFN e for e < routed ; E_e(u) = u for e >= routed

**Which experts are mine.** ``experts_held = (lo, hi)`` is the range of routed
experts whose weights this module holds — one chip's share under expert
parallelism (the weights carry the logical axis ``experts``). The router keeps
its full width and its k; the layer computes what its own experts give for its
own tokens, plus the zero-compute experts (a token's home chip applies them:
they cost no exchange). What absent experts would add is left out — on one
chip the layer runs without its exchange and nothing stands in for it.

Everything but the router runs at ``dtype`` (bfloat16 weights and
activations, float32 accumulation, norms and softmax in float32); the router's
product and softmax are float32 as published. Attention is one kernel where it
can run (``ops/latent_attention.py``: one TPU device, published widths, whole
128-row tiles) and computed in blocks over the queries elsewhere
(``ops/ring_attention.blocked_causal_attention``); the held experts are
grouped products with no capacity limit (``ops/grouped.py``).

Two flax collections leave the forward when asked for (``mutable=``):
``stats`` — the step's routing counts summed over layers (``moe``) and how
many of its attention blocks ran the kernel (``attn``), which the joint
trainer reads where it reads the loss — and ``routing`` — every layer's
choices, for a comparison with a reference.

**What ``pangu_moe.py`` and ``smallthinker.py`` take from here** is
LongCat's own: :class:`LatentAttention` (with its ``scores`` scope) and
:func:`sow_attention` (Pangu), and :func:`held_experts` (both: the held
experts' weights and ``held_expert_ffn``). They read a config by the
published names the families have in common; each model keeps its own
``route`` and layer class. What no family owns — projections, the dense FFN,
norms, the embedding, an expert layer's pad mask and counts, the configs'
``experts_held`` — is ``llm/layers.py``'s.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from deepdfa_tpu.llm.layers import (
    DenseFFN,
    HeldRange,
    RMSNorm,
    embed_tokens,
    mask_pads,
    proj,
    rope_cos_sin,
    sow_and_count,
    sow_stats,
)
from deepdfa_tpu.ops.dispatch import kernel_mode
from deepdfa_tpu.ops.grouped import held_expert_ffn
from deepdfa_tpu.ops.ring_attention import blocked_causal_attention

__all__ = [
    "LongcatConfig",
    "LongcatModel",
    "longcat_flash",
    "tiny_longcat",
    "rope_interleaved",
    "route",
    "LatentAttention",
    "held_experts",
    "sow_attention",
]


@dataclasses.dataclass(frozen=True)
class LongcatConfig(HeldRange):
    """Published ``config.json`` keys (defaults: LongCat-Flash-Omni's language
    model) plus the TPU-side knobs at the end."""

    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    routed_scaling_factor: float = 6.0
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    moe_topk: int = 12
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10_000_000.0
    dtype: str = "bfloat16"
    # [lo, hi) of the routed experts held here; None = all of them
    experts_held: tuple[int, int] | None = None
    attn_block_q: int = 256  # queries per attention block
    # sorted assignments a trip of the expert loop takes (ops/grouped.py): the rows
    # of each grouped product and of the gather, so the loop's memory; the combine
    # walks a trip in blocks and only as far as it holds assignments
    moe_chunk_rows: int = 4096

    def __post_init__(self):
        self._check_held()

    @property
    def router_width(self) -> int:
        return self.n_routed_experts + self.zero_expert_num


def longcat_flash(**kw) -> LongcatConfig:
    """meituan-longcat/LongCat-Flash-Omni's language model, as published."""
    return LongcatConfig(**kw)


def tiny_longcat(**kw) -> LongcatConfig:
    """Test-size config (CI): every mechanism present, 8 routed + 4 zero
    experts, top-3."""
    defaults = dict(
        vocab_size=320, hidden_size=64, ffn_hidden_size=128, expert_ffn_hidden_size=32,
        num_layers=2, num_attention_heads=4, kv_lora_rank=16, q_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
        zero_expert_num=4, moe_topk=3, max_position_embeddings=256, dtype="float32",
        attn_block_q=16, moe_chunk_rows=32,
    )
    defaults.update(kw)
    return LongcatConfig(**defaults)


def rope_interleaved(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Rotary embedding over interleaved pairs ``(x[2i], x[2i+1])`` (the
    DeepSeek-V2 convention). x: [..., d]; cos/sin: broadcastable [..., d/2]."""
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def route(x: jnp.ndarray, w_r: jnp.ndarray, bias: jnp.ndarray, cfg: LongcatConfig):
    """``(choice [t, k] int32, gates [t, k] float32)`` over all routed and
    zero-compute experts. The product and the softmax are float32; the
    correction bias enters the choice only; the gates are the chosen
    probabilities times the scaling factor, not renormalised."""
    logits = jnp.dot(x.astype(jnp.float32), w_r, precision=lax.Precision.HIGHEST)
    p = jax.nn.softmax(logits, axis=-1)
    _, choice = lax.top_k(p + bias, cfg.moe_topk)
    gates = cfg.routed_scaling_factor * jnp.take_along_axis(p, choice, axis=-1)
    return choice.astype(jnp.int32), gates


def _fused_attention(cfg, seq_len: int) -> bool | None:
    """The ``interpret`` flag for the latent-attention kernel, or ``None``
    where ``blocked_causal_attention`` has to run (``ops/dispatch.py``)."""
    return kernel_mode("latent_attention", seq_len, cfg.num_attention_heads,
                       cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim)


class LatentAttention(nn.Module):
    """Multi-head latent attention: queries and keys/values through low-rank
    latents, a rope part of the key shared by all heads. ``cfg`` is either
    decoder's: the sizes by their published names, ``mla_scale_q_lora`` /
    ``mla_scale_kv_lora`` (whether a normed latent is scaled), ``dtype``,
    ``attn_block_q``."""

    cfg: Any

    @nn.compact
    def __call__(self, x, attn_mask, positions):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        h, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
        b, s, _ = x.shape
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dtype=dtype, name=name)
        scale = lambda on, rank: math.sqrt(cfg.hidden_size / rank) if on else 1.0

        c_q = proj(cfg.q_lora_rank, ("embed", "latent"), cfg, "q_a_proj")(x)
        c_q = (norm("q_a_norm")(c_q) * scale(cfg.mla_scale_q_lora, cfg.q_lora_rank)).astype(dtype)
        q = proj(h * (dn + dr), ("latent", "heads"), cfg, "q_b_proj")(c_q)
        q = q.reshape(b, s, h, dn + dr)

        ckv = proj(cfg.kv_lora_rank + dr, ("embed", "latent"), cfg, "kv_a_proj")(x)
        c_kv, k_r = ckv[..., :cfg.kv_lora_rank], ckv[..., cfg.kv_lora_rank:]
        c_kv = (norm("kv_a_norm")(c_kv)
                * scale(cfg.mla_scale_kv_lora, cfg.kv_lora_rank)).astype(dtype)
        kv = proj(h * (dn + dv), ("latent", "heads"), cfg, "kv_b_proj")(c_kv)

        cos, sin = rope_cos_sin(positions, dr, cfg.rope_theta)  # [b, s, dr/2]
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
        q_n, q_r = q[..., :dn], rope_interleaved(q[..., dn:], cos, sin)
        k_r = rope_interleaved(k_r[:, :, None, :], cos, sin)
        fused = _fused_attention(cfg, s)
        with jax.named_scope("scores"):
            if fused is not None:
                from deepdfa_tpu.ops.latent_attention import latent_attention

                # heads side by side, as the projections give and take them
                out = latent_attention(
                    q_n.reshape(b, s, h * dn), q_r.reshape(b, s, h * dr), k_r[:, :, 0], kv,
                    attn_mask, num_heads=h, interpret=fused)
            else:
                kv = kv.reshape(b, s, h, dn + dv)
                k = jnp.concatenate(
                    [kv[..., :dn], jnp.broadcast_to(k_r, (b, s, h, dr))], axis=-1)
                out = blocked_causal_attention(
                    jnp.concatenate([q_n, q_r], axis=-1), k, kv[..., dn:],
                    kv_mask=attn_mask, block_q=cfg.attn_block_q).reshape(b, s, h * dv)
        return proj(cfg.hidden_size, ("heads", "embed"), cfg, "o_proj")(out)


def held_experts(layer: nn.Module, x, choice, gates, width: int, activation=jax.nn.silu):
    """``(out [t, d] float32, computed)``: the part of an expert layer's
    result that the experts held here (``layer.cfg.held``) give for the
    tokens ``x``. The held experts' weights, ``width`` wide, are ``layer``'s
    own parameters (called from its compact ``__call__``); ``activation`` is
    their gate's."""
    cfg = layer.cfg
    dtype = jnp.dtype(cfg.dtype)
    lo, hi = cfg.held
    n, d = hi - lo, x.shape[-1]
    fan_in = nn.initializers.variance_scaling(
        1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1, batch_axis=(0,))
    expert = lambda name, shape, axes: layer.param(
        name, nn.with_logical_partitioning(fan_in, axes), shape, dtype)
    w_gate = expert("experts_gate", (n, d, width), ("experts", "embed", "expert_mlp"))
    w_up = expert("experts_up", (n, d, width), ("experts", "embed", "expert_mlp"))
    w_down = expert("experts_down", (n, width, d), ("experts", "expert_mlp", "embed"))
    # only what departs from ``held_expert_ffn``'s defaults is named: the plantings of
    # ``benchmark/tools/prove_frozen*.py`` wrap it by the two keywords it has always had
    other = {"activation": activation} if activation is not jax.nn.silu else {}
    if cfg.holds_every_expert:
        other["whole"] = True
    with jax.named_scope("held_experts"):
        return held_expert_ffn(
            x, choice, gates, w_gate, w_up, w_down, lo=lo, rows=cfg.moe_chunk_rows, **other)


class ExpertLayer(nn.Module):
    """Router over all routed + zero-compute experts; the held experts'
    part of the result plus the zero-compute experts' (module docstring)."""

    cfg: LongcatConfig

    @nn.compact
    def __call__(self, u, token_mask):
        cfg = self.cfg
        b, s, d = u.shape
        w_r = self.param(
            "router_kernel",
            nn.with_logical_partitioning(nn.initializers.lecun_normal(), ("embed", "router")),
            (d, cfg.router_width), jnp.float32)
        bias = self.param(
            "router_bias",
            nn.with_logical_partitioning(nn.initializers.zeros_init(), ("router",)),
            (cfg.router_width,), jnp.float32)
        x = u.reshape(b * s, d)
        choice, gates = mask_pads(*route(x, w_r, bias, cfg), token_mask)
        out, computed = held_experts(self, x, choice, gates, cfg.expert_ffn_hidden_size)
        zero = choice >= cfg.n_routed_experts
        out = out + _zero_experts(x, gates, zero)
        counts = sow_and_count(self, choice, computed, (b, s), zero)
        return out.astype(jnp.dtype(cfg.dtype)).reshape(b, s, d), counts


def _zero_experts(x, gates, zero):
    """``sum over the zero-compute choices of gate * x``: an identity expert
    returns its input, scaled by its gate alone. float32."""
    return jnp.sum(jnp.where(zero, gates, 0.0), axis=-1, keepdims=True) * x.astype(jnp.float32)


class LongcatLayer(nn.Module):
    """Two attention blocks, two dense FFNs, the expert layer on a shortcut."""

    cfg: LongcatConfig

    @nn.compact
    def __call__(self, h, attn_mask, positions):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dtype=dtype, name=name)
        for i in (0, 1):
            a = h + LatentAttention(cfg, name=f"attn_{i}")(
                norm(f"attn_norm_{i}")(h), attn_mask, positions)
            u = norm(f"ffn_norm_{i}")(a)
            if i == 0:
                shortcut, counts = ExpertLayer(cfg, name="moe")(u, attn_mask)
            h = a + DenseFFN(cfg, cfg.ffn_hidden_size, name=f"ffn_{i}")(u)
            if i == 1:
                h = h + shortcut
        return nn.with_logical_constraint(h, ("batch", "seq", "embed")), counts


def sow_attention(model: nn.Module, blocks: int, seq_len: int) -> None:
    """Which attention the step ran, into ``stats`` (``RobertaEncoder``'s
    names): the model's latent-attention ``blocks`` and how many of them ran
    the kernel — all or none, by ``_fused_attention``."""
    blocks = jnp.int32(blocks)
    fused = _fused_attention(model.cfg, seq_len) is not None
    sow_stats(model, "attn", {"layers": blocks, "fused": blocks * fused})


class LongcatModel(nn.Module):
    """Decoder stack -> final-norm hidden states [b, s, hidden], the joint
    trainer's encoder contract (``llm.apply(params, input_ids, pad_mask)``)."""

    cfg: LongcatConfig

    @nn.compact
    def __call__(self, input_ids, attn_mask=None, positions=None):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(input_ids.shape[1]), input_ids.shape)
        x = embed_tokens(cfg, input_ids)
        totals = None
        for i in range(cfg.num_layers):
            x, counts = LongcatLayer(cfg, name=f"layers_{i}")(x, attn_mask, positions)
            totals = counts if totals is None else jax.tree.map(jnp.add, totals, counts)
        sow_stats(self, "moe", totals)  # summed over layers
        sow_attention(self, 2 * cfg.num_layers, input_ids.shape[1])  # two blocks a layer
        return RMSNorm(cfg.rms_norm_eps, dtype=dtype, name="norm")(x)
