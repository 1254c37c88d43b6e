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

**A layer's position-wise halves skip the chunks of positions that hold no
real token.** ``pre`` (input norm, the q, k, v and gate projections, q/k
norms, RoPE, gates) and ``post`` (``o_proj``, residual, post-attention norm,
MLP, residual) read no other position, so each runs as a lifted ``nn.scan``
over chunks of :data:`DENSE_BLOCK` positions (a whole row where that does not
divide the block; :func:`dense_chunks`), the layer's weights broadcast, and an
``nn.cond`` computes a chunk only where it holds a real token. A chunk of pads
yields zeros from ``pre`` and its own input from ``post``: no real token reads
a pad. The retention between the halves takes whole rows. ``init`` runs both
halves over the whole block (``BrumbyLayer.whole``), so it draws the leaves it
always drew. The width is the one ``scripts/bench_brumby_layer.py`` times
best on the chip at the benchmark cell's shape.

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
(``chunks_computed`` times the chunk), ``tokens_real`` and ``tokens_dense``
(the positions the halves computed: live chunks times their width), each
summed over the layers. The scopes ``layers.pre/retention.project/proj``,
``layers/retention.retain/kernel``, ``layers.post/o_proj`` and
``layers.post/mlp`` are what ``benchmark/tools/program_trace.py`` sums.
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
DENSE_BLOCK = 512  # positions a chunk of a layer's position-wise halves (module docstring)


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
    """The retention's weights, ``project`` (q, k, v and gate projections,
    q/k norms, RoPE and gates) and ``retain`` (the retention over whole rows);
    ``o_proj`` is the layer's to call."""

    cfg: BrumbyConfig

    def setup(self):
        cfg = self.cfg
        h, hk, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        self.q_proj = _proj(h * d, ("embed", "heads"), cfg, "q_proj")
        self.k_proj = _proj(hk * d, ("embed", "kv_heads"), cfg, "k_proj")
        self.v_proj = _proj(hk * d, ("embed", "kv_heads"), cfg, "v_proj")
        self.g_bias = self.param(  # float32: it holds the half-lives
            "g_bias", nn.with_logical_partitioning(gate_bias_init, (None,)), (hk,), jnp.float32)
        self.g_proj = _proj(hk, ("embed", None), cfg, "g_proj")
        self.q_norm = RMSNorm(cfg.rms_norm_eps, dtype=jnp.float32, name="q_norm")
        self.k_norm = RMSNorm(cfg.rms_norm_eps, dtype=jnp.float32, name="k_norm")
        self.o_proj = _proj(cfg.hidden_size, ("heads", "embed"), cfg, "o_proj")

    def project(self, x, positions):
        """``(q, k, v, log_g)`` of ``x`` [b, s, hidden] at ``positions`` [b, s]."""
        cfg = self.cfg
        h, hk, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        b, s, _ = x.shape
        dtype = jnp.dtype(cfg.dtype)
        with jax.named_scope("proj"):
            q = self.q_proj(x).reshape(b, s, h, d)
            k = self.k_proj(x).reshape(b, s, hk, d)
            log_g = jax.nn.log_sigmoid(self.g_proj(x).astype(jnp.float32) + self.g_bias)
            cos, sin = rope_cos_sin(positions, d, cfg.rope_theta)
            q = apply_rope(self.q_norm(q), cos, sin).astype(dtype)
            k = apply_rope(self.k_norm(k), cos, sin).astype(dtype)
        return q.reshape(b, s, h * d), k.reshape(b, s, hk * d), self.v_proj(x), log_g

    def retain(self, q, k, v, log_g, mask):
        """The retention's ``o`` [b, s, heads x d] over whole rows."""
        cfg = self.cfg
        with jax.named_scope("kernel"):
            return power_retention(q, k, v, log_g, mask, chunk=cfg.retention_chunk,
                                   interpret=_fused_retention(cfg, mask.shape[1]))


def dense_chunks(mask: jnp.ndarray):
    """``(live [chunks] bool, width)``: a layer's position-wise halves walk
    the ``b * s`` positions of ``mask`` [b, s] in chunks of ``width`` —
    :data:`DENSE_BLOCK` where it divides ``s``, else the whole row — and a
    chunk is live where it holds a real token."""
    b, s = mask.shape
    width = DENSE_BLOCK if s % DENSE_BLOCK == 0 else s
    return jnp.any(mask.reshape(b * s // width, width), axis=1), width


def _over_live(layer: nn.Module, half, skip, live, *chunks):
    """``half(layer, *c)`` for the chunks ``c`` of ``chunks`` (each [n, ...],
    chunk by chunk on the leading axis) where ``live``, ``skip(*c)``
    elsewhere: one lifted scan over the chunks, the layer's parameters
    broadcast into each trip; stacked on the leading axis."""
    def trip(mdl, carry, xs):
        on, *c = xs
        return carry, nn.cond(on, half, lambda _, *c: skip(*c), mdl, *c)

    scan = nn.scan(trip, variable_broadcast="params", split_rngs={"params": False})
    return scan(layer, None, (live, *chunks))[1]


class BrumbyLayer(nn.Module):
    """The retention and the MLP, each behind a norm: one body of the
    ``nn.scan`` (carry ``x``; ``mask`` and ``positions`` broadcast). The two
    position-wise halves, ``pre`` and ``post``, run over the chunks of
    positions that hold a real token (:func:`dense_chunks`); the retention
    between them over whole rows."""

    cfg: BrumbyConfig

    def setup(self):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        self.input_norm = RMSNorm(cfg.rms_norm_eps, dtype=dtype, name="input_norm")
        self.retention = PowerRetention(cfg)
        self.post_attn_norm = RMSNorm(cfg.rms_norm_eps, dtype=dtype, name="post_attn_norm")
        self.mlp = DenseFFN(cfg, cfg.intermediate_size)

    def pre(self, x, positions):
        """The input norm and the retention's projections: ``(q, k, v, log_g)``."""
        return self.retention.project(self.input_norm(x), positions)

    def post(self, x, o):
        """``o_proj``, the residual, the post-attention norm, the MLP and the
        residual: the layer's output."""
        a = x + self.retention.o_proj(o)
        return a + self.mlp(self.post_attn_norm(a))

    def whole(self, x, mask, positions):
        """The layer with both halves over the whole block: what ``init`` runs."""
        return self.post(x, self.retention.retain(*self.pre(x, positions), mask))

    def __call__(self, x, mask, positions):
        cfg = self.cfg
        if self.is_initializing():  # init draws the leaves it always drew
            return nn.with_logical_constraint(self.whole(x, mask, positions),
                                              ("batch", "seq", "embed")), None
        h, hk, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        dtype = jnp.dtype(cfg.dtype)
        b, s, _ = x.shape
        live, width = dense_chunks(mask)
        cut = lambda a: a.reshape(live.shape[0], 1, width, *a.shape[2:])  # a chunk is a [1, width] row
        rows = lambda a: a.reshape(b, s, *a.shape[3:])
        nothing = lambda c, _: (  # a chunk of pads yields nothing a real token reads
            jnp.zeros((1, width, h * d), dtype), jnp.zeros((1, width, hk * d), dtype),
            jnp.zeros((1, width, hk * d), dtype), jnp.zeros((1, width, hk), jnp.float32))
        q, k, v, log_g = map(rows, _over_live(self, BrumbyLayer.pre, nothing, live, cut(x),
                                              cut(positions)))
        o = self.retention.retain(q, k, v, log_g, mask)
        y = rows(_over_live(self, BrumbyLayer.post, lambda c, _: c, live, cut(x), cut(o)))
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
        live, width = dense_chunks(attn_mask)
        self.sow("stats", "retention", {
            "layers": jnp.int32(layers), "fused": jnp.int32(layers * fused),
            "chunks_needed": layers * chunks_needed(attn_mask, chunk),
            "chunks_computed": computed, "tokens_visited": computed * chunk,
            "tokens_real": layers * jnp.sum(attn_mask, dtype=jnp.int32),
            "tokens_dense": layers * jnp.sum(live, dtype=jnp.int32) * width,
        }, reduce_fn=lambda _, new: new, init_fn=dict)
        return RMSNorm(cfg.rms_norm_eps, dtype=jnp.dtype(cfg.dtype), name="norm")(x)
