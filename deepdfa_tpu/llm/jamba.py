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

**A layer's position-wise halves skip the chunks of positions that hold no
real token.** ``pre`` (the input norm, then ``in_proj`` or q / k / v) and
``post`` (``out_proj`` or ``o_proj``, the residual, the FFN norm, the MLP,
the residual) read no other position, so each runs over chunks of
:data:`DENSE_BLOCK` positions (a whole row where that does not divide the
block) as ``llm/layers.py``'s ``over_live``, whose ``lax.cond`` computes a
chunk only where it holds a real token and writes it in place. A chunk of pads
yields zeros from ``pre`` and its own input from ``post``; the mixer's
sequence part between them (the convolution, ``x_proj``, the inner norms,
``dt_proj`` and the ``scan`` scope, or the attention's scores) takes whole
rows, and both masks above keep every real token from reading a pad, so the
real tokens' states are the whole block's. ``init`` runs both halves over the
whole block (``JambaLayer.whole``), so it draws the leaves it always drew.

Precision as the other decoders': weights and activations ``dtype``, products
accumulate in float32; RMSNorm, softplus, ``exp(delta A)``, the state and its
whole recurrence, the ``C`` reduction and softmax in float32; ``A_log``, ``D``
and ``b_dt`` are float32 leaves (Mamba's convention); each sub-layer's output
is rounded once. On one TPU device the ``scan`` scope (softplus, recurrence,
gate) is one kernel with the same precision (``ops/selective_scan.gated_scan``,
at shapes its ``supports`` takes). ``tie_word_embeddings``: the embedding is
the only vocabulary-sized leaf, and an encoder that hands out final-norm
states builds no output head.

``stats/ssm`` (the Mamba layers) and ``stats/attn`` (the attention layers),
read by the joint trainer where it reads the loss: ``layers``, ``fused`` (the
layers whose mixer ran a kernel), ``tokens_real`` (real positions) and
``tokens_dense`` (the positions the halves computed: live chunks times their
width), each summed over the layers of the kind. The scopes
``layers_i/mamba.mix/{conv,x_proj,dt_proj,scan}`` and
``layers_i/attn.mix/scores`` are what ``benchmark/tools/program_trace.py``
sums; the halves' products are ``JambaLayer.pre/mamba.project/in_proj``,
``JambaLayer.post/mamba.out/out_proj``, ``JambaLayer.post/mlp`` (and the
attention layers' alike) under ``jit(_half_over_live)``, one trace for every
layer of a kind.
"""

from __future__ import annotations

import dataclasses
import functools

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepdfa_tpu.llm.layers import (
    DenseFFN,
    RMSNorm,
    dense_chunks,
    embed_tokens,
    over_live,
    proj,
    sow_stats,
)
from deepdfa_tpu.ops.dispatch import kernel_mode
from deepdfa_tpu.ops.ring_attention import blocked_causal_attention
from deepdfa_tpu.ops.selective_scan import causal_conv1d, gated_scan, selective_scan

__all__ = ["JambaConfig", "JambaModel", "jamba2_3b", "tiny_jamba", "dt_bias_init"]

DENSE_BLOCK = 512  # positions a chunk of a layer's position-wise halves (module docstring)


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
    """The Mamba-1 mixer with Jamba's three inner norms (module docstring):
    ``in_proj`` (:meth:`project`), the sequence part over whole rows
    (:meth:`mix`) and ``out_proj`` (:meth:`out`)."""

    cfg: JambaConfig

    def setup(self):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        di, n, r, k = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.mamba_d_conv
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dtype=dtype, name=name)
        channels = lambda name, init, shape, dt: self.param(
            name, nn.with_logical_partitioning(init, (None,) * (len(shape) - 1) + ("mlp",)),
            shape, dt)

        self.in_proj = proj(2 * di, ("embed", "mlp"), cfg, "in_proj")
        self.conv_kernel = channels(
            "conv_kernel", nn.initializers.lecun_normal(in_axis=0, out_axis=1), (k, di), dtype)
        self.conv_bias = channels("conv_bias", nn.initializers.zeros_init(), (di,), dtype)
        self.x_proj = proj(r + 2 * n, ("mlp", None), cfg, "x_proj")
        self.dt_norm, self.b_norm, self.c_norm = norm("dt_norm"), norm("b_norm"), norm("c_norm")
        self.dt_proj = proj(di, (None, "mlp"), cfg, "dt_proj")
        self.dt_bias = channels("dt_bias", dt_bias_init, (di,), jnp.float32)
        self.A_log = self.param(
            "A_log", nn.with_logical_partitioning(_a_log_init, ("mlp", None)), (di, n), jnp.float32)
        self.D = channels("D", nn.initializers.ones_init(), (di,), jnp.float32)
        self.out_proj = proj(cfg.hidden_size, ("mlp", "embed"), cfg, "out_proj")

    def project(self, x):
        """``(in_proj(x),)``: ``u`` and ``z`` side by side."""
        return (self.in_proj(x),)

    def mix(self, uz, mask):
        """``y`` [b, s, d_inner] of ``in_proj``'s output over whole rows: the
        convolution, ``x_proj``, the inner norms, ``dt_proj`` and the ``scan``
        scope (softplus, the recurrence, the gate)."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        di, n, r = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
        u, z = uz[..., :di], uz[..., di:]
        with jax.named_scope("conv"):
            c = causal_conv1d(u, self.conv_kernel, self.conv_bias, mask)
        rbc = self.x_proj(c)
        dt = self.dt_norm(rbc[..., :r])
        b_in, c_in = self.b_norm(rbc[..., r:r + n]), self.c_norm(rbc[..., r + n:])
        dt = self.dt_proj(dt)
        interpret = _fused_scan(cfg, uz.shape[1])
        with jax.named_scope("scan"):
            if interpret is None:  # the plain form, under the names ``benchmark/tools`` plant faults in
                delta = jax.nn.softplus(dt.astype(jnp.float32) + self.dt_bias)
                y = selective_scan(c, delta, -jnp.exp(self.A_log), b_in, c_in, self.D, mask)
                return (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))).astype(dtype)
            # the same three steps as one kernel, ``z`` read where it lies in ``uz``
            return gated_scan(c, dt, self.dt_bias, -jnp.exp(self.A_log), b_in, c_in, self.D, uz,
                              mask, interpret=interpret)

    def out(self, y):
        return self.out_proj(y)


class MultiQueryAttention(nn.Module):
    """Causal attention whose query heads share ``num_key_value_heads``
    key/value heads, with no positional encoding: q / k / v
    (:meth:`project`), the scores over whole rows (:meth:`mix`) and ``o_proj``
    (:meth:`out`)."""

    cfg: JambaConfig

    def setup(self):
        cfg = self.cfg
        h, hk, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        self.q_proj = proj(h * d, ("embed", "heads"), cfg, "q_proj")
        self.k_proj = proj(hk * d, ("embed", "kv_heads"), cfg, "k_proj")
        self.v_proj = proj(hk * d, ("embed", "kv_heads"), cfg, "v_proj")
        self.o_proj = proj(cfg.hidden_size, ("heads", "embed"), cfg, "o_proj")

    def project(self, x):
        """``(q, k, v)``, heads side by side."""
        return self.q_proj(x), self.k_proj(x), self.v_proj(x)

    def mix(self, q, k, v, mask):
        """The attention's output [b, s, heads x head_dim] over whole rows."""
        cfg = self.cfg
        h, hk, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        b, s, _ = q.shape
        with jax.named_scope("scores"):
            out = blocked_causal_attention(
                q.reshape(b, s, h, d), k.reshape(b, s, hk, d), v.reshape(b, s, hk, d),
                kv_mask=mask, block_q=cfg.attn_block_q)
        return out.reshape(b, s, h * d)

    def out(self, y):
        return self.o_proj(y)


class JambaLayer(nn.Module):
    """One mixer — attention or Mamba — and one MLP, each behind a norm. The
    two position-wise halves, :meth:`pre` and :meth:`post`, run over the
    chunks of positions that hold a real token (module docstring); the
    mixer's sequence part between them over whole rows."""

    cfg: JambaConfig
    attention: bool

    def setup(self):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        self.input_norm = RMSNorm(cfg.rms_norm_eps, dtype=dtype, name="input_norm")
        self.mixer = (MultiQueryAttention(cfg, name="attn") if self.attention
                      else MambaMixer(cfg, name="mamba"))
        self.ffn_norm = RMSNorm(cfg.rms_norm_eps, dtype=dtype, name="ffn_norm")
        self.mlp = DenseFFN(cfg, cfg.intermediate_size, name="mlp")

    def pre(self, h):
        """The input norm and the mixer's projections."""
        return self.mixer.project(self.input_norm(h))

    def post(self, h, y):
        """The mixer's output projection, the residual, the FFN norm, the MLP
        and the residual: the layer's output."""
        a = h + self.mixer.out(y)
        return a + self.mlp(self.ffn_norm(a))

    def whole(self, h, mask):
        """The layer with both halves over the whole block: what ``init`` runs."""
        return self.post(h, self.mixer.mix(*self.pre(h), mask))

    def __call__(self, h, mask):
        if self.is_initializing():  # init draws the leaves it always drew
            return nn.with_logical_constraint(self.whole(h, mask), ("batch", "seq", "embed"))
        b, s, _ = h.shape
        live, width = dense_chunks(mask, DENSE_BLOCK)
        cut = lambda a: a.reshape(live.shape[0], 1, width, *a.shape[2:])  # a chunk is a [1, width] row
        rows = lambda a: a.reshape(b, s, *a.shape[3:])
        kind, params = (type(self), self.cfg, self.attention), self.variables["params"]
        parts = _half_over_live(*kind, "pre", params, live, cut(h))
        y = self.mixer.mix(*map(rows, parts), mask)
        h = rows(_half_over_live(*kind, "post", params, live, cut(h), cut(y)))
        return nn.with_logical_constraint(h, ("batch", "seq", "embed"))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _half_over_live(cls, cfg, attention, half, params, live, *chunks):
    """``cls(cfg, attention)``'s ``half`` (``"pre"`` or ``"post"``) on the
    layer's ``params`` over the chunks of ``chunks`` (each [n, 1, width, ...])
    that are ``live`` (``over_live``); elsewhere ``pre`` yields zeros and
    ``post`` its input. A function of arrays under ``jit``, so it is traced
    and lowered once a kind of layer and a half, not once a layer: the stack's
    28 layers are unrolled, and a loop over a bound module's methods would be
    traced 56 times a program. Its trace is reused across programs, so it
    reads no name that a planting patches."""
    layer = cls(cfg, attention, parent=None)
    run = lambda *c: layer.apply({"params": params}, *c, method=half)
    if half == "post":
        return over_live(run, lambda c, _: c, live, *chunks)
    out = jax.eval_shape(run, *(c[0] for c in chunks))
    nothing = lambda *_: jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), out)
    return over_live(run, nothing, live, *chunks)


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
        # by ``_fused_scan``; the attention layers none (``blocked_causal_attention``);
        # ``tokens_real`` / ``tokens_dense`` the real positions / those the halves computed
        # (live chunks times their width), summed over the layers of the kind
        n_attn = len(cfg.attention_layers)
        n_ssm = cfg.num_hidden_layers - n_attn
        ssm_fused = n_ssm * (_fused_scan(cfg, input_ids.shape[1]) is not None)
        live, width = dense_chunks(attn_mask, DENSE_BLOCK)
        real = jnp.sum(attn_mask, dtype=jnp.int32)
        dense = jnp.sum(live, dtype=jnp.int32) * width
        for name, layers, fused in (("ssm", n_ssm, ssm_fused), ("attn", n_attn, 0)):
            sow_stats(self, name, {"layers": jnp.int32(layers), "fused": jnp.int32(fused),
                                   "tokens_real": layers * real, "tokens_dense": layers * dense})
        return RMSNorm(cfg.rms_norm_eps, dtype=jnp.dtype(cfg.dtype), name="norm")(x)
