"""A decoder whose attention is **degree-2 power retention** (the Brumby
layer) in Flax.

The seventh encoder stack and the first whose mixer is linear attention: a
pre-norm causal decoder of identical layers, each a Qwen3-style grouped-query
layer whose softmax attention is replaced by power retention (Manifest AI,
arXiv 2507.04239; ``ops/power_retention.py``), then a dense SiLU-gated MLP.
Config keys are the published ``config.json``'s
(``BrumbyConfig.from_hf_dict`` reads one directly).

Layer input ``x`` [tokens, hidden]; RMSNorm ``N`` (learned scale); no biases
but the gate's::

    n = N_in(x)
    q = N_q(n W_q) [heads x d] ; k = N_k(n W_k) [kv heads x d] ; v = n W_v     per-head q/k norm (Qwen3's)
    q, k rotated (rotate-half over d, theta, positions counting real tokens)
    log g_t = logsigmoid(n_t W_g + b_g)                 one gate per key/value head
    w_tj = (q_t . k_j / sqrt d)^2 * exp(sum_{j<u<=t} log g_u)   for real j <= t
    o_t = sum_j w_tj v_j / (sum_j w_tj + eps)           query head h reads key/value head h // (heads / kv heads)
    a = x + o W_o
    y = a + W_down(silu(W_gate m) * (W_up m)) ,  m = N_post(a)

**The layers are one ``nn.scan`` body**: the stack's weights carry a leading
layer axis (``layers/...`` [L, ...]), the step lowers one layer, and the
retention kernel is one instruction of the program, so the device trace sums
its events under one name. Under left padding everything starts at a row's
first real token: a pad adds nothing to the retention state, decays nothing,
and RoPE's positions count real tokens.

Precision: weights and activations ``dtype``, products accumulate in float32;
RMSNorm, the q/k norms, the rotation, the gates and the retention's state and
ratio in float32 (q and k handed to the retention in ``dtype``); the gate's
bias a float32 leaf. The retention is one Pallas kernel where it can run
(``ops/power_retention_kernel.py``: one TPU device, heads of 128, whole
chunks; chunks of leading pads never visited) and the plain chunked form
elsewhere. It has no backward: the decoder is frozen.

``stats/retention`` (read by the joint trainer where it reads the loss):
``layers``, ``fused`` (the layers whose retention ran the kernel: all or
none), ``chunks_needed`` (a row's chunks that hold a real token),
``chunks_computed`` (those the path that ran visited), ``tokens_visited``
(``chunks_computed`` times the chunk) and ``tokens_real``, each summed over
the layers. The scopes ``layers/retention/proj``,
``layers/retention/kernel`` and ``layers/mlp`` are what
``benchmark/tools/program_trace.py`` sums.
"""

from __future__ import annotations

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepdfa_tpu.llm import roberta
from deepdfa_tpu.llm.llama import RMSNorm, apply_rope, rope_cos_sin
from deepdfa_tpu.llm.longcat import DenseFFN, _proj, embed_tokens
from deepdfa_tpu.ops.power_retention import (
    chunks_computed,
    chunks_needed,
    power_retention,
    supports,
)

__all__ = ["BrumbyConfig", "BrumbyModel", "BrumbyLayer", "brumby_14b", "tiny_brumby",
           "gate_bias_init"]

HALF_LIVES = (64, 8192)  # the gate biases' half-lives, log-uniform across the kv heads (tokens)


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    """Published ``config.json`` keys (defaults: Brumby-14B-Base) plus the
    TPU-side knobs at the end."""

    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    hidden_act: str = "silu"
    attention_bias: bool = False
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1_000_000.0
    rope_scaling: None = None
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    retention_chunk: int = 128  # positions a chunk of the retention (both forms)

    def __post_init__(self):
        if self.hidden_act != "silu" or self.attention_bias:
            raise ValueError("hidden_act other than silu or attention_bias=True is another "
                             "layer: none is written here")
        if self.rope_scaling is not None:
            raise ValueError("rope_scaling is not built here: the published value is null")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(f"{self.num_attention_heads} query heads do not share "
                             f"{self.num_key_value_heads} key/value heads evenly")

    @classmethod
    def from_hf_dict(cls, d: dict):
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})


def brumby_14b(**kw) -> BrumbyConfig:
    """manifestai/Brumby-14B-Base, as published."""
    return BrumbyConfig(**kw)


def tiny_brumby(**kw) -> BrumbyConfig:
    """Test-size config (CI): 2 layers, 4 query heads over 2 key/value heads
    of 16, chunks of 16 positions."""
    defaults = dict(
        vocab_size=320, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        max_position_embeddings=256, dtype="float32", retention_chunk=16,
    )
    defaults.update(kw)
    return BrumbyConfig(**defaults)


def gate_bias_init(key, shape, dtype=jnp.float32):
    """``b_g`` with half-lives log-uniform over :data:`HALF_LIVES` across the
    key/value heads (evenly spaced in log, no draw): ``sigmoid(b) =
    2^(-1/half-life)``. Random biases would give gates that forget within a
    token."""
    lo, hi = (math.log(v) for v in HALF_LIVES)
    life = jnp.exp(jnp.linspace(lo, hi, shape[-1], dtype=jnp.float32))
    g = jnp.exp2(-1.0 / life)
    return jnp.broadcast_to(jnp.log(g) - jnp.log1p(-g), shape).astype(dtype)


def _fused_retention(cfg: BrumbyConfig, seq_len: int) -> bool | None:
    """The ``interpret`` flag for the retention kernel, or ``None`` where the
    plain form has to run: no kernel here (the rule is
    ``roberta._attention_kernel``'s: one TPU device) or a shape it does not
    take."""
    interpret = roberta._attention_kernel()
    if interpret is None or not supports(seq_len, cfg.num_attention_heads, cfg.num_key_value_heads,
                                         cfg.head_dim, cfg.retention_chunk):
        return None
    return interpret


class PowerRetention(nn.Module):
    """Projections, q/k norms, RoPE and gates, then the retention (module
    docstring), then ``o_proj``."""

    cfg: BrumbyConfig

    @nn.compact
    def __call__(self, x, mask, positions):
        cfg = self.cfg
        h, hk, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        b, s, _ = x.shape
        dtype = jnp.dtype(cfg.dtype)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dtype=jnp.float32, name=name)
        with jax.named_scope("proj"):
            q = _proj(h * d, ("embed", "heads"), cfg, "q_proj")(x).reshape(b, s, h, d)
            k = _proj(hk * d, ("embed", "kv_heads"), cfg, "k_proj")(x).reshape(b, s, hk, d)
            v = _proj(hk * d, ("embed", "kv_heads"), cfg, "v_proj")(x)
            g_bias = self.param(  # float32: it holds the half-lives
                "g_bias", nn.with_logical_partitioning(gate_bias_init, (None,)), (hk,),
                jnp.float32)
            g = _proj(hk, ("embed", None), cfg, "g_proj")(x)
            log_g = jax.nn.log_sigmoid(g.astype(jnp.float32) + g_bias)
            cos, sin = rope_cos_sin(positions, d, cfg.rope_theta)
            q = apply_rope(norm("q_norm")(q), cos, sin).astype(dtype)
            k = apply_rope(norm("k_norm")(k), cos, sin).astype(dtype)
        with jax.named_scope("kernel"):
            o = power_retention(q.reshape(b, s, h * d), k.reshape(b, s, hk * d), v, log_g, mask,
                                chunk=cfg.retention_chunk, interpret=_fused_retention(cfg, s))
        return _proj(cfg.hidden_size, ("heads", "embed"), cfg, "o_proj")(o)


class BrumbyLayer(nn.Module):
    """The retention and the MLP, each behind a norm: one body of the
    ``nn.scan`` (carry ``x``; ``mask`` and ``positions`` broadcast)."""

    cfg: BrumbyConfig

    @nn.compact
    def __call__(self, x, mask, positions):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dtype=dtype, name=name)
        a = x + PowerRetention(cfg, name="retention")(norm("input_norm")(x), mask, positions)
        y = a + DenseFFN(cfg, cfg.intermediate_size, name="mlp")(norm("post_attn_norm")(a))
        return nn.with_logical_constraint(y, ("batch", "seq", "embed")), None


class BrumbyModel(nn.Module):
    """Decoder stack -> final-norm hidden states [b, s, hidden], the joint
    trainer's encoder contract (``llm.apply(params, input_ids, pad_mask)``)."""

    cfg: BrumbyConfig

    @nn.compact
    def __call__(self, input_ids, attn_mask=None):
        cfg = self.cfg
        b, s = input_ids.shape
        if s % cfg.retention_chunk:
            raise ValueError(f"chunks of {cfg.retention_chunk} positions do not tile the "
                             f"block of {s}")
        if attn_mask is None:
            attn_mask = jnp.ones((b, s), bool)
        attn_mask = attn_mask.astype(bool)
        # a row's first real token is position 0
        positions = jnp.maximum(jnp.cumsum(attn_mask.astype(jnp.int32), axis=-1) - 1, 0)
        x = embed_tokens(cfg, input_ids)
        stack = nn.scan(
            BrumbyLayer, variable_axes={"params": 0}, split_rngs={"params": True},
            in_axes=(nn.broadcast, nn.broadcast), length=cfg.num_hidden_layers,
            metadata_params={nn.PARTITION_NAME: None})
        x, _ = stack(cfg, name="layers")(x, attn_mask, positions)
        layers, chunk = cfg.num_hidden_layers, cfg.retention_chunk
        fused = _fused_retention(cfg, s) is not None  # every layer's or none's
        computed = layers * chunks_computed(attn_mask, chunk, fused)
        self.sow("stats", "retention", {
            "layers": jnp.int32(layers), "fused": jnp.int32(layers * fused),
            "chunks_needed": layers * chunks_needed(attn_mask, chunk),
            "chunks_computed": computed, "tokens_visited": computed * chunk,
            "tokens_real": layers * jnp.sum(attn_mask, dtype=jnp.int32),
        }, reduce_fn=lambda _, new: new, init_fn=dict)
        return RMSNorm(cfg.rms_norm_eps, dtype=jnp.dtype(cfg.dtype), name="norm")(x)
