"""A hybrid state-space / attention decoder whose layers differ by **kind of
mixer** (the Jamba layer) in Flax.

The fifth encoder stack and the first whose mixer is a recurrence: a pre-norm
causal decoder in which layer ``i`` mixes by multi-query attention where
``(i - attn_layer_offset) % attn_layer_period == 0`` and by a Mamba-1
selective scan everywhere else, with a plain gated MLP after every mixer
(``num_experts == 1``: the family's routed variant is not built, and a config
that asks for it raises). Config keys are the published ``config.json``'s
(``JambaConfig.from_hf_dict`` reads one directly).

Layer input ``h`` [tokens, hidden]; RMSNorm ``N`` (learned scale); ``m`` the
pad mask as 0/1 per position; no biases but the convolution's and ``dt``'s::

    a  = h + Mixer_i( N_in(h) )
    h' = a + W_down( silu(W_gate u) * (W_up u) )          u = N_ff(a)
    Mamba(x): [u, z] = x W_in ; u = u * m
              c_t = silu( sum_j w_j * u_{t-(k-1)+j} + b_conv ) per channel, u before position 0 is 0 ; c = c * m
              [r, B, C] = c W_x  (dt_rank | d_state | d_state) ; r = N_dt(r) ; B = N_B(B) ; C = N_C(C)
              delta = softplus(r W_dt + b_dt) ; A = -exp(A_log)
              s_t = exp(delta_t (x) A) * s_{t-1} + (delta_t * c_t) (x) B_t ,  s_{-1} = 0
              y_t = s_t . C_t + D * c_t ;  out = (y * silu(z)) W_out
    Attn(x):  q = x W_q -> heads x head_dim ; k = x W_k, v = x W_v -> kv heads x head_dim, shared by the query heads
              NO positional encoding (the model takes position from its Mamba layers)
              scores / sqrt(head_dim), causal, pads masked as keys, softmax in float32 ; W_o

With both masks a pad adds nothing to a Mamba layer's state (``delta * c`` is
0 there and a state of 0 decays to 0), so a left-padded row's real tokens read
what the row alone would — exactly (``ops/selective_scan.py``). The three
norms inside the mixer are Jamba's own addition to Mamba-1.

Precision as the other decoders': weights and activations ``dtype``, products
accumulate in float32; RMSNorm, softplus, ``exp(delta A)``, the state and its
whole recurrence, the ``C`` reduction and softmax in float32; ``A_log``, ``D``
and ``b_dt`` are float32 leaves (Mamba's convention); each sub-layer's output
is rounded once. On one TPU device the ``scan`` scope (softplus, recurrence,
gate) is one kernel with the same precision (``ops/selective_scan.gated_scan``,
at shapes its ``supports`` takes; ``stats/ssm`` counts the layers that ran
it). ``tie_word_embeddings``: the embedding is the only vocabulary-sized
leaf, and an encoder that hands out final-norm states builds no output head.

The scopes ``layers_i/mamba/{in_proj,conv,scan,out_proj}``,
``layers_i/attn/scores`` and ``layers_i/mlp`` are what
``benchmark/tools/program_trace.py`` sums.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepdfa_tpu.llm.layers import DenseFFN, RMSNorm, embed_tokens, proj, sow_stats
from deepdfa_tpu.ops.dispatch import kernel_mode
from deepdfa_tpu.ops.ring_attention import blocked_causal_attention
from deepdfa_tpu.ops.selective_scan import causal_conv1d, gated_scan, selective_scan

__all__ = ["JambaConfig", "JambaModel", "jamba2_3b", "tiny_jamba", "dt_bias_init"]


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    """Published ``config.json`` keys (defaults: AI21-Jamba2-3B) plus the
    TPU-side knobs at the end."""

    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    expert_layer_period: int = 2  # select nothing while ``num_experts`` is 1
    expert_layer_offset: int = 1
    num_experts: int = 1
    num_experts_per_tok: int = 1
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = True
    dtype: str = "bfloat16"
    attn_block_q: int = 256  # queries per attention block

    def __post_init__(self):
        if self.num_experts != 1:
            raise ValueError(
                f"num_experts {self.num_experts}: the family's routed variant (experts in "
                "the layers expert_layer_period / expert_layer_offset select) is not built "
                "here, and running it dense would be another model")
        if not self.mamba_conv_bias or self.mamba_proj_bias:
            raise ValueError("mamba_conv_bias=False or mamba_proj_bias=True is another "
                             "layer: none is written here")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError(f"hidden_size {self.hidden_size} is no whole number of "
                             f"{self.num_attention_heads} heads")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(f"{self.num_attention_heads} query heads do not share "
                             f"{self.num_key_value_heads} key/value heads evenly")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    def is_attention(self, layer: int) -> bool:
        """Whether ``layer`` mixes by attention (a Mamba layer otherwise)."""
        return (layer - self.attn_layer_offset) % self.attn_layer_period == 0

    @property
    def attention_layers(self) -> tuple[int, ...]:
        return tuple(i for i in range(self.num_hidden_layers) if self.is_attention(i))

    @classmethod
    def from_hf_dict(cls, d: dict):
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})


def jamba2_3b(**kw) -> JambaConfig:
    """ai21labs/AI21-Jamba2-3B, as published."""
    return JambaConfig(**kw)


def tiny_jamba(**kw) -> JambaConfig:
    """Test-size config (CI): both kinds of layer and two periods of the
    pattern (attention at layers 2 and 6 of 8), multi-query attention."""
    defaults = dict(
        vocab_size=320, hidden_size=64, intermediate_size=128, num_hidden_layers=8,
        num_attention_heads=4, num_key_value_heads=1, attn_layer_period=4, attn_layer_offset=2,
        mamba_d_state=8, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=8,
        max_position_embeddings=256, dtype="float32", attn_block_q=16,
    )
    defaults.update(kw)
    return JambaConfig(**defaults)


def dt_bias_init(key, shape, dtype=jnp.float32, dt_min=1e-3, dt_max=1e-1):
    """Mamba-1's: ``softplus^-1`` of a log-uniform draw in ``[dt_min, dt_max]``,
    so that ``delta`` starts among the step sizes trained models use."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                 * (jnp.log(dt_max) - jnp.log(dt_min)) + jnp.log(dt_min))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log_init(key, shape, dtype=jnp.float32):
    """Mamba-1's: ``A = -(1 .. d_state)`` on every channel."""
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape).astype(dtype)


def _fused_scan(cfg: JambaConfig, seq_len: int) -> bool | None:
    """The ``interpret`` flag for the selective-scan kernel, or ``None`` where
    the plain form has to run (``ops/dispatch.py``)."""
    return kernel_mode("selective_scan", seq_len, cfg.d_inner, cfg.mamba_d_state)


class MambaMixer(nn.Module):
    """The Mamba-1 mixer with Jamba's three inner norms (module docstring)."""

    cfg: JambaConfig

    @nn.compact
    def __call__(self, x, mask):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        di, n, r, k = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.mamba_d_conv
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dtype=dtype, name=name)
        channels = lambda name, init, shape, dt: self.param(
            name, nn.with_logical_partitioning(init, (None,) * (len(shape) - 1) + ("mlp",)),
            shape, dt)

        uz = proj(2 * di, ("embed", "mlp"), cfg, "in_proj")(x)
        u, z = uz[..., :di], uz[..., di:]
        w = channels("conv_kernel", nn.initializers.lecun_normal(in_axis=0, out_axis=1), (k, di), dtype)
        b = channels("conv_bias", nn.initializers.zeros_init(), (di,), dtype)
        with jax.named_scope("conv"):
            c = causal_conv1d(u, w, b, mask)
        rbc = proj(r + 2 * n, ("mlp", None), cfg, "x_proj")(c)
        dt = norm("dt_norm")(rbc[..., :r])
        b_in, c_in = norm("b_norm")(rbc[..., r:r + n]), norm("c_norm")(rbc[..., r + n:])
        dt = proj(di, (None, "mlp"), cfg, "dt_proj")(dt)
        dt_bias = channels("dt_bias", dt_bias_init, (di,), jnp.float32)
        a_log = self.param(
            "A_log", nn.with_logical_partitioning(_a_log_init, ("mlp", None)), (di, n), jnp.float32)
        d_skip = channels("D", nn.initializers.ones_init(), (di,), jnp.float32)
        interpret = _fused_scan(cfg, x.shape[1])
        with jax.named_scope("scan"):
            if interpret is None:  # the plain form, under the names ``benchmark/tools`` plant faults in
                delta = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
                y = selective_scan(c, delta, -jnp.exp(a_log), b_in, c_in, d_skip, mask)
                y = (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))).astype(dtype)
            else:  # the same three steps as one kernel, ``z`` read where it lies in ``uz``
                y = gated_scan(c, dt, dt_bias, -jnp.exp(a_log), b_in, c_in, d_skip, uz, mask,
                               interpret=interpret)
        return proj(cfg.hidden_size, ("mlp", "embed"), cfg, "out_proj")(y)


class MultiQueryAttention(nn.Module):
    """Causal attention whose query heads share ``num_key_value_heads``
    key/value heads, with no positional encoding."""

    cfg: JambaConfig

    @nn.compact
    def __call__(self, x, mask):
        cfg = self.cfg
        h, hk, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        b, s, _ = x.shape
        q = proj(h * d, ("embed", "heads"), cfg, "q_proj")(x).reshape(b, s, h, d)
        k = proj(hk * d, ("embed", "kv_heads"), cfg, "k_proj")(x).reshape(b, s, hk, d)
        v = proj(hk * d, ("embed", "kv_heads"), cfg, "v_proj")(x).reshape(b, s, hk, d)
        with jax.named_scope("scores"):
            out = blocked_causal_attention(q, k, v, kv_mask=mask, block_q=cfg.attn_block_q)
        return proj(cfg.hidden_size, ("heads", "embed"), cfg, "o_proj")(out.reshape(b, s, h * d))


class JambaLayer(nn.Module):
    """One mixer — attention or Mamba — and one MLP, each behind a norm."""

    cfg: JambaConfig
    attention: bool

    @nn.compact
    def __call__(self, h, mask):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dtype=dtype, name=name)
        mixer = (MultiQueryAttention(cfg, name="attn") if self.attention
                 else MambaMixer(cfg, name="mamba"))
        a = h + mixer(norm("input_norm")(h), mask)
        h = a + DenseFFN(cfg, cfg.intermediate_size, name="mlp")(norm("ffn_norm")(a))
        return nn.with_logical_constraint(h, ("batch", "seq", "embed"))


class JambaModel(nn.Module):
    """Decoder stack -> final-norm hidden states [b, s, hidden], the joint
    trainer's encoder contract (``llm.apply(params, input_ids, pad_mask)``)."""

    cfg: JambaConfig

    @nn.compact
    def __call__(self, input_ids, attn_mask=None):
        cfg = self.cfg
        if attn_mask is None:
            attn_mask = jnp.ones(input_ids.shape, bool)
        attn_mask = attn_mask.astype(bool)
        x = embed_tokens(cfg, input_ids)
        for i in range(cfg.num_hidden_layers):
            x = JambaLayer(cfg, cfg.is_attention(i), name=f"layers_{i}")(x, attn_mask)
        # which mixers the step ran, into ``stats`` (the other encoders' names and channel):
        # ``fused`` counts the layers whose mixer ran a kernel — the Mamba layers all or none,
        # by ``_fused_scan``; the attention layers none (``blocked_causal_attention``)
        n_attn = len(cfg.attention_layers)
        n_ssm = cfg.num_hidden_layers - n_attn
        ssm_fused = n_ssm * (_fused_scan(cfg, input_ids.shape[1]) is not None)
        for name, layers, fused in (("ssm", n_ssm, ssm_fused), ("attn", n_attn, 0)):
            sow_stats(self, name, {"layers": jnp.int32(layers), "fused": jnp.int32(fused)})
        return RMSNorm(cfg.rms_norm_eps, dtype=jnp.dtype(cfg.dtype), name="norm")(x)
