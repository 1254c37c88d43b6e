"""A sandwich-norm, latent-attention decoder whose layers differ by FFN kind
(the openPangu-Ultra-MoE layer) in Flax.

The fourth encoder stack, and the second sparse one beside ``longcat.py``: a
causal decoder with **four norms a layer** (one on each branch's input and one
on its *output*, before it joins the residual), whose first
``first_k_dense_replace`` layers carry a dense FFN and the rest an expert
layer: a **shared expert** every token passes through beside routed experts
chosen by **sigmoid** scores, top-k, the gates renormalised over the chosen
and scaled. Config keys are the published ``config.json``'s
(``PanguMoeConfig.from_hf_dict`` reads one directly).

Layer input ``h`` [tokens, hidden]; RMSNorm ``N``, no biases::

    a  = h + N_post_attn( MLA( N_in(h) ) )
    h' = a + N_post_mlp( F( N_pre_mlp(a) ) )     F = DenseFFN for layer < first_k_dense_replace, else MoE
    MLA(x): c_q = N(W_qa x) ; q = W_qb c_q -> heads x (nope | rope)          (no latent scales)
            [c_kv | k_r] = W_kva x ; c_kv = N(c_kv) ; [k_n | v] = W_kvb c_kv -> heads x (nope | v)
            RoPE on q's rope part and on k_r (interleaved pairs), k_r shared by all heads
            scores = (q_n.k_n + q_r.k_r) / sqrt(nope + rope), causal, pad-masked
    MoE(u): s = sigmoid(W_r u) over the routed experts, float32
            choice = top-k of s ; g = scaling * s[choice] / (sum s[choice] + 1e-20)
            out = Shared(u) + sum over choice of g_e E_e(u)     E, Shared: gated FFNs, expert width

**Which experts are mine** is ``longcat.py``'s statement: ``experts_held =
(lo, hi)`` is this chip's range under expert parallelism; the router keeps its
full width and its k, the renormalisation is over all k chosen, held or not,
and what the absent experts would add is left out. The shared expert, like
attention and the dense FFN, is whole on every chip.

From ``longcat.py`` this decoder takes ``LatentAttention`` (kernel choice and
``scores`` scope included), the ``attn`` counts and the held experts' weights
and grouped products; from ``llm/layers.py`` ``DenseFFN`` (the dense layers'
and the shared expert's), the norms, the embedding, the config's
``experts_held`` half and the rest of an expert layer's second half — the
``routing`` choices and the ``stats`` counts (same names; ``zero`` is always 0
here, ``layers`` counts expert layers only, ``attn`` has one block a layer).
The multi-token-prediction module of the published model
(``num_nextn_predict_layers``) predicts a further token through the output
head; an encoder that hands out final-norm states has no head and leaves it out.
"""

from __future__ import annotations

import dataclasses

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
    sow_and_count,
    sow_stats,
)
from deepdfa_tpu.llm.longcat import LatentAttention, held_experts, sow_attention

__all__ = ["PanguMoeConfig", "PanguMoeModel", "openpangu_ultra_moe", "tiny_pangu_moe", "route"]


@dataclasses.dataclass(frozen=True)
class PanguMoeConfig(HeldRange):
    """Published ``config.json`` keys (defaults: openPangu-Ultra-MoE-718B)
    plus the TPU-side knobs at the end."""

    vocab_size: int = 153600
    hidden_size: int = 7680
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    sandwich_norm: bool = True
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 25_600_000.0
    dtype: str = "bfloat16"
    # [lo, hi) of the routed experts held here; None = all of them
    experts_held: tuple[int, int] | None = None
    attn_block_q: int = 256  # queries per attention block
    # sorted assignments a trip of the expert loop takes (ops/grouped.py): the rows
    # of each grouped product and of the gather, so the loop's memory; the combine
    # walks a trip in blocks and only as far as it holds assignments
    moe_chunk_rows: int = 4096

    # ``LatentAttention``'s switches: this family scales no latent
    mla_scale_q_lora = False
    mla_scale_kv_lora = False

    def __post_init__(self):
        self._check_held()
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError(f"first_k_dense_replace {self.first_k_dense_replace} is no count "
                             f"of the {self.num_hidden_layers} layers")
        if not self.sandwich_norm:
            raise ValueError("sandwich_norm=False is another layer: none is written here")


def openpangu_ultra_moe(**kw) -> PanguMoeConfig:
    """FreedomIntelligence/openPangu-Ultra-MoE-718B, as published."""
    return PanguMoeConfig(**kw)


def tiny_pangu_moe(**kw) -> PanguMoeConfig:
    """Test-size config (CI): every mechanism present, 1 dense + 2 expert
    layers, 8 routed experts top-3, 1 shared, 4 heads."""
    defaults = dict(
        vocab_size=320, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
        num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=4, kv_lora_rank=16,
        q_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        n_routed_experts=8, num_experts_per_tok=3, max_position_embeddings=256,
        dtype="float32", attn_block_q=16, moe_chunk_rows=32,
    )
    defaults.update(kw)
    return PanguMoeConfig(**defaults)


def route(x: jnp.ndarray, w_r: jnp.ndarray, cfg: PanguMoeConfig):
    """``(choice [t, k] int32, gates [t, k] float32)`` over all routed
    experts. The product and the sigmoid are float32; the gates are the chosen
    scores, renormalised over the k chosen (``norm_topk_prob``) and scaled."""
    logits = jnp.dot(x.astype(jnp.float32), w_r, precision=lax.Precision.HIGHEST)
    top, choice = lax.top_k(jax.nn.sigmoid(logits), cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return choice.astype(jnp.int32), cfg.routed_scaling_factor * top


class ExpertLayer(nn.Module):
    """The shared expert for every token plus the held routed experts' part
    (module docstring)."""

    cfg: PanguMoeConfig

    @nn.compact
    def __call__(self, u, token_mask):
        cfg = self.cfg
        b, s, d = u.shape
        w_r = self.param(
            "router_kernel",
            nn.with_logical_partitioning(nn.initializers.lecun_normal(), ("embed", "router")),
            (d, cfg.n_routed_experts), jnp.float32)
        x = u.reshape(b * s, d)
        with jax.named_scope("router"):
            choice, gates = mask_pads(*route(x, w_r, cfg), token_mask)
        out, computed = held_experts(self, x, choice, gates, cfg.moe_intermediate_size)
        shared = DenseFFN(
            cfg, cfg.n_shared_experts * cfg.moe_intermediate_size, name="shared_expert")(u)
        counts = sow_and_count(self, choice, computed, (b, s))
        out = shared.astype(jnp.float32) + out.reshape(b, s, d)
        return out.astype(jnp.dtype(cfg.dtype)), counts


class PanguMoeLayer(nn.Module):
    """One attention block and one FFN — dense or experts — each between a
    norm on its input and a norm on its output."""

    cfg: PanguMoeConfig
    dense: bool

    @nn.compact
    def __call__(self, h, attn_mask, positions):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dtype=dtype, name=name)
        attn = LatentAttention(cfg, name="attn")(norm("input_norm")(h), attn_mask, positions)
        a = h + norm("post_attn_norm")(attn)
        u = norm("pre_mlp_norm")(a)
        if self.dense:
            f, counts = DenseFFN(cfg, cfg.intermediate_size, name="ffn")(u), None
        else:
            f, counts = ExpertLayer(cfg, name="moe")(u, attn_mask)
        h = a + norm("post_mlp_norm")(f)
        return nn.with_logical_constraint(h, ("batch", "seq", "embed")), counts


class PanguMoeModel(nn.Module):
    """Decoder stack -> final-norm hidden states [b, s, hidden], the joint
    trainer's encoder contract (``llm.apply(params, input_ids, pad_mask)``)."""

    cfg: PanguMoeConfig

    @nn.compact
    def __call__(self, input_ids, attn_mask=None, positions=None):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(input_ids.shape[1]), input_ids.shape)
        x = embed_tokens(cfg, input_ids)
        totals = None
        for i in range(cfg.num_hidden_layers):
            x, counts = PanguMoeLayer(cfg, i < cfg.first_k_dense_replace, name=f"layers_{i}")(
                x, attn_mask, positions)
            if counts is not None:  # a leading layer has no router
                totals = counts if totals is None else jax.tree.map(jnp.add, totals, counts)
        if totals is not None:  # ``LongcatModel``'s collections: summed over the expert layers
            sow_stats(self, "moe", totals)
        sow_attention(self, cfg.num_hidden_layers, input_ids.shape[1])  # one block a layer
        return RMSNorm(cfg.rms_norm_eps, dtype=dtype, name="norm")(x)
