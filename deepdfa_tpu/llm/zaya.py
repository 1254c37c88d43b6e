"""A decoder of compressed convolutional attention and top-1 experts behind an
MLP router whose state crosses the layers (the ZAYA1 layer) in Flax.

The eighth encoder stack: a pre-norm causal decoder in which every layer is
CCA (``ops/cca.py``: attention inside a compressed latent, its queries and
keys mixed along the sequence by two causal convolutions) and then one of 16
SiLU experts or none, picked by a three-product router that carries its state
from layer to layer ("exponential depth averaging", EDA). Config keys are the
published ``config.json``'s (``ZayaConfig.from_hf_dict`` reads one directly).

Layer input ``x`` [tokens, hidden]; RMSNorm ``N`` (learned scale); no biases::

    n  = N_in(x)
    q~, k~, v = n W_q [heads x d], n W_k [kv heads x d], n W_v [2 x d]
    q^, k^, v = the CCA prologue (ops/cca.py): two causal convolutions, qk-mean,
                L2 norm and the key head's temperature, RoPE over the first
                partial_rotary_factor of each head, value head 1 the previous token's
    a  = x + softmax(q^ k^T / sqrt(d) + causal, pad mask) v W_o
    m  = N_post(a)
    r_l  = m W_down                              [router_hidden_size], float32
    r~_l = (1 - gamma_l) r_l + gamma_l r~_{l-1}  EDA (r~_0 = r_0; gamma_l = sigmoid of a learned scalar)
    l  = gelu(gelu(r~_l W_1) W_2) W_3            [num_experts + 1]: the experts and the skip
    c  = argmax(l + b) ; g = softmax(l)[c]       b: the balancing bias, selection only
    y  = a + g E_c(m) ; y = a + g m where c is the skip
    E_e(m) = (silu(m W_gate_e) * (m W_up_e)) W_down_e

The skip (the published family's "residual-scaled mixture of depths") is
``longcat.py``'s zero-compute expert: it returns its input scaled by its gate
and costs no product, and the routing counts name it ``zero``. The layers'
router state ``r~`` is an input and an output of each layer
(:class:`ZayaLayer`), so a layer stays a function of what it is handed.

**Which experts are mine** is ``longcat.py``'s statement: ``experts_held = (lo,
hi)``, the router keeps its width, what absent experts would add is left out;
held whole (the cell) the skip's choices are handed to
``ops/grouped.held_expert_ffn`` as no choice at all, so it combines every held
row by its gather.

Under left padding everything starts at a row's first real token: the
convolutions and the value shift read zeros before it, a pad is routed
nowhere and is no key, and RoPE's positions count real tokens.

Precision as the other decoders': weights and activations ``dtype``, products
accumulate in float32; RMSNorm, the CCA prologue, softmax and the experts'
gated product in float32; the router's weights float32 and its three products
``Precision.HIGHEST``. Attention is ``ops/gqa_attention.py``'s kernel where it
can run (``ops/dispatch.py``: one TPU device, heads of 128, whole 128-row
tiles) — the temperature rides in ``k^`` — and
``ops/ring_attention.blocked_causal_attention`` elsewhere.
``tie_word_embeddings``: an encoder that hands out final-norm states builds no
head, tied or not.

``stats`` (read by the joint trainer where it reads the loss): ``moe`` — the
routing counts ``layers.sow_and_count`` gives, the skip's as ``zero``, summed
over layers, with ``gathered`` and ``gather_slots`` as ``smallthinker.py``
counts them — ``cca`` — ``layers`` and ``fused`` (those whose attention ran the
kernel: all or none) — and ``attn`` — ``pairs_needed`` and ``pairs_computed``
(``smallthinker.needed_pairs`` / ``computed_pairs``, every layer). The scopes
``layers_i/attn/mix`` (the prologue), ``layers_i/attn/scores``,
``layers_i/router`` and ``layers_i/moe/held_experts`` are what
``benchmark/tools/program_trace.py`` sums.
"""

from __future__ import annotations

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from deepdfa_tpu.llm import longcat
from deepdfa_tpu.llm.layers import (
    HeldRange,
    RMSNorm,
    embed_tokens,
    mask_pads,
    proj,
    rope_cos_sin,
    sow_and_count,
    sow_stats,
)
from deepdfa_tpu.llm.smallthinker import computed_pairs, needed_pairs
from deepdfa_tpu.ops import cca
from deepdfa_tpu.ops.dispatch import kernel_mode
from deepdfa_tpu.ops.grouped import gather_slots
from deepdfa_tpu.ops.ring_attention import blocked_causal_attention

__all__ = ["ZayaConfig", "ZayaModel", "ZayaLayer", "CCAttention", "ZayaRouter", "zaya1_8b",
           "tiny_zaya", "eda", "router_logits", "route"]

HI = lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class ZayaConfig(HeldRange):
    """Published ``config.json`` keys (defaults: Zyphra/ZAYA1-8B) plus the
    TPU-side knobs at the end. ``rope_theta`` is the published
    ``rope_parameters.hybrid.rope_theta``, the kind every layer is."""

    vocab_size: int = 262272
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2  # the depthwise convolution's kernel
    cca_time1: int = 2  # the grouped convolution's kernel
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5_000_000.0
    router_hidden_size: int = 256
    num_experts: int = 16
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 2048
    hidden_act: str = "silu"
    layer_types: tuple[str, ...] = ("hybrid",) * 40
    sliding_window: None = None
    attention_bias: bool = False
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    dtype: str = "bfloat16"
    # [lo, hi) of the experts held here; None = all of them
    experts_held: tuple[int, int] | None = None
    attn_block_q: int = 512  # queries per attention block
    # sorted assignments a trip of the expert loop takes (ops/grouped.py)
    moe_chunk_rows: int = 4096

    def __post_init__(self):
        self._check_held()
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        kinds = set(self.layer_types[:self.num_hidden_layers])
        if len(self.layer_types) < self.num_hidden_layers or kinds != {"hybrid"}:
            raise ValueError(f"layer_types {kinds}: only 'hybrid' layers (CCA, then the experts) "
                             f"are built, one for each of the {self.num_hidden_layers}")
        if (self.cca_time0, self.cca_time1) != (2, 2):
            raise ValueError("the CCA convolutions are built with kernels of 2, as published")
        if self.num_key_value_heads != 2 or self.num_attention_heads % 2:
            raise ValueError("the value shift is built for two key/value heads, as published")
        if self.num_experts_per_tok != 1 or self.hidden_act != "silu":
            raise ValueError("the router is built top-1 over SiLU experts, as published")
        if self.sliding_window is not None or self.attention_bias:
            raise ValueError("no window and no attention bias are built: the published values "
                             "are null and false")

    @classmethod
    def from_hf_dict(cls, d: dict):
        """The published keys; ``rope_parameters`` read for the hybrid layers'."""
        d = dict(d)
        rope = d.pop("rope_parameters", None)
        if rope is not None:
            hybrid = rope["hybrid"]
            d.setdefault("partial_rotary_factor", hybrid["partial_rotary_factor"])
            if (hybrid.get("rope_type", "default") != "default"
                    or hybrid["partial_rotary_factor"] != d["partial_rotary_factor"]):
                raise ValueError(f"rope_parameters.hybrid {hybrid} is not built here")
            d["rope_theta"] = hybrid["rope_theta"]
        return super().from_hf_dict(d)

    @property
    def n_routed_experts(self) -> int:  # ``HeldRange``'s and the drivers' name for it
        return self.num_experts

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)


def zaya1_8b(**kw) -> ZayaConfig:
    """Zyphra/ZAYA1-8B, as published."""
    return ZayaConfig(**kw)


def tiny_zaya(**kw) -> ZayaConfig:
    """Test-size config (CI): 4 layers, 4 query heads over 2 key/value heads
    of 16, 8 experts and the skip, a router 16 wide, attention in blocks of 16."""
    defaults = dict(
        vocab_size=320, hidden_size=64, num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, router_hidden_size=16, num_experts=8,
        moe_intermediate_size=32, layer_types=("hybrid",) * 4, max_position_embeddings=256,
        dtype="float32", attn_block_q=16, moe_chunk_rows=32,
    )
    defaults.update(kw)
    return ZayaConfig(**defaults)


def eda(r, prev, gamma):
    """Exponential depth averaging: this layer's router state mixed with the
    one the layer before handed on."""
    return (1.0 - gamma) * r + gamma * prev


def router_logits(r, w_1, w_2, w_3):
    """The router MLP over its (averaged) state: [t, experts + 1], float32."""
    dot = lambda x, w: jnp.dot(x, w, precision=HI)
    return dot(jax.nn.gelu(dot(jax.nn.gelu(dot(r, w_1), approximate=False), w_2),
                           approximate=False), w_3)


def route(logits, bias):
    """``(choice [t, 1] int32, gate [t, 1] float32)``: the largest of ``logits
    + bias`` (the bias chooses, never weighs), its gate the softmax over all
    of ``logits`` at the chosen."""
    choice = jnp.argmax(logits + bias, axis=-1)[:, None].astype(jnp.int32)
    return choice, jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), choice, axis=-1)


def _fused_attention(cfg: ZayaConfig, seq_len: int) -> bool | None:
    """The ``interpret`` flag for the grouped-query attention kernel, or
    ``None`` where ``blocked_causal_attention`` has to run (``ops/dispatch.py``)."""
    return kernel_mode("gqa_attention", seq_len, cfg.num_attention_heads,
                       cfg.num_key_value_heads, cfg.head_dim)


class CCAttention(nn.Module):
    """Compressed convolutional attention: latent projections, the prologue of
    ``ops/cca.py``, causal attention, one product back up (module docstring)."""

    cfg: ZayaConfig

    @nn.compact
    def __call__(self, x, mask, positions):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        h, hk, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        b, s, _ = x.shape
        q0 = proj(h * d, ("embed", "heads"), cfg, "q_proj")(x)
        k0 = proj(hk * d, ("embed", "kv_heads"), cfg, "k_proj")(x)
        v = proj(hk * d, ("embed", "kv_heads"), cfg, "v_proj")(x)
        param = lambda name, init, shape, axes: self.param(
            name, nn.with_logical_partitioning(init, axes), shape, dtype)
        w1 = param("conv_depthwise", nn.initializers.normal(0.5),
                   (cfg.cca_time0, (h + hk) * d), ("norm", "latent"))
        w2 = param("conv_grouped", nn.initializers.normal(1.0 / math.sqrt(cfg.cca_time1 * d)),
                   (cfg.cca_time1, h + hk, d, d), ("norm", "latent", None, None))
        tau = param("temperature", nn.initializers.constant(math.sqrt(d)), (hk,), ("norm",))
        cos, sin = rope_cos_sin(positions, cfg.rotary_dim, cfg.rope_theta)
        with jax.named_scope("mix"):
            q, k, v = cca.prologue(q0, k0, v, w1, w2, tau, mask, cos, sin, heads=h, dtype=dtype)
        fused = _fused_attention(cfg, s)
        with jax.named_scope("scores"):
            if fused is not None:
                from deepdfa_tpu.ops.gqa_attention import gqa_attention

                # heads side by side, as the projections give and take them
                out = gqa_attention(
                    q.reshape(b, s, h * d), k.reshape(b, s, hk * d), v.reshape(b, s, hk * d),
                    mask, num_kv_heads=hk, interpret=fused)
            else:
                out = blocked_causal_attention(
                    q, k, v, kv_mask=mask, block_q=cfg.attn_block_q).reshape(b, s, h * d)
        return proj(cfg.hidden_size, ("heads", "embed"), cfg, "o_proj")(out)


class ZayaRouter(nn.Module):
    """``(choice, gate, r~)`` for the tokens ``m`` [t, hidden] from the
    router MLP over the EDA state; ``prev`` is the layer before's ``r~``
    (``None`` in the first layer, which has no ``eda`` weight)."""

    cfg: ZayaConfig

    @nn.compact
    def __call__(self, m, prev):
        cfg = self.cfg
        width, options = cfg.router_hidden_size, cfg.num_experts + 1
        f32 = lambda name, init, shape, axes: self.param(
            name, nn.with_logical_partitioning(init, axes), shape, jnp.float32)
        w_down = f32("down", nn.initializers.lecun_normal(), (m.shape[-1], width),
                     ("embed", "router"))
        w_1 = f32("mlp_1", nn.initializers.lecun_normal(), (width, width), ("router", None))
        w_2 = f32("mlp_2", nn.initializers.lecun_normal(), (width, width), ("router", None))
        w_3 = f32("mlp_3", nn.initializers.lecun_normal(), (width, options), ("router", None))
        bias = f32("bias", nn.initializers.zeros_init(), (options,), ("router",))
        r = jnp.dot(m.astype(jnp.float32), w_down, precision=HI)
        if prev is not None:
            r = eda(r, prev, jax.nn.sigmoid(f32("eda", nn.initializers.zeros_init(), (), ())))
        choice, gate = route(router_logits(r, w_1, w_2, w_3), bias)
        return choice, gate, r


class ZayaExperts(nn.Module):
    """The held experts' part of the result for ``m`` [b, s, hidden] and the
    skip's, for choices already made (pads: -1)."""

    cfg: ZayaConfig

    @nn.compact
    def __call__(self, m, choice, gate):
        cfg = self.cfg
        b, s, d = m.shape
        x = m.reshape(b * s, d)
        skip = choice == cfg.num_experts
        experts = jnp.where(skip, -1, choice)  # the skip is no expert's row
        out, computed = longcat.held_experts(self, x, experts, gate, cfg.moe_intermediate_size)
        out = out + longcat._zero_experts(x, gate, skip)
        counts = sow_and_count(self, choice, computed, (b, s), skip)
        counts["gathered"] = counts["held"] * cfg.holds_every_expert
        counts["gather_slots"] = gather_slots(experts) * cfg.holds_every_expert
        return out.astype(jnp.dtype(cfg.dtype)).reshape(b, s, d), counts


class ZayaLayer(nn.Module):
    """CCA, then the router and the experts, each behind a norm:
    ``(x, r~ of the layer before) -> (y, r~, counts)``."""

    cfg: ZayaConfig

    @nn.compact
    def __call__(self, x, mask, positions, prev):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dtype=jnp.dtype(cfg.dtype), name=name)
        a = x + CCAttention(cfg, name="attn")(norm("input_norm")(x), mask, positions)
        m = norm("post_attn_norm")(a)
        b, s, d = m.shape
        choice, gate, r = ZayaRouter(cfg, name="router")(m.reshape(b * s, d), prev)
        y, counts = ZayaExperts(cfg, name="moe")(m, *mask_pads(choice, gate, mask))
        return nn.with_logical_constraint(a + y, ("batch", "seq", "embed")), r, counts


class ZayaModel(nn.Module):
    """Decoder stack -> final-norm hidden states [b, s, hidden], the joint
    trainer's encoder contract (``llm.apply(params, input_ids, pad_mask)``)."""

    cfg: ZayaConfig

    @nn.compact
    def __call__(self, input_ids, attn_mask=None):
        cfg = self.cfg
        b, s = input_ids.shape
        if attn_mask is None:
            attn_mask = jnp.ones((b, s), bool)
        attn_mask = attn_mask.astype(bool)
        # a row's first real token is position 0
        positions = jnp.maximum(jnp.cumsum(attn_mask.astype(jnp.int32), axis=-1) - 1, 0)
        x = embed_tokens(cfg, input_ids)
        r = totals = None
        for i in range(cfg.num_hidden_layers):
            x, r, counts = ZayaLayer(cfg, name=f"layers_{i}")(x, attn_mask, positions, r)
            totals = counts if totals is None else jax.tree.map(jnp.add, totals, counts)
        sow_stats(self, "moe", totals)  # summed over layers
        layers = jnp.int32(cfg.num_hidden_layers)
        fused = _fused_attention(cfg, s) is not None  # every layer's attention or none's
        sow_stats(self, "cca", {"layers": layers, "fused": layers * fused})
        sow_stats(self, "attn", {
            "pairs_needed": layers * needed_pairs(attn_mask, None),
            "pairs_computed": layers * computed_pairs(cfg, attn_mask, None),
        })
        return RMSNorm(cfg.rms_norm_eps, dtype=jnp.dtype(cfg.dtype), name="norm")(x)
