"""A grouped-query decoder whose layers differ by **kind of attention** and
whose router reads the layer's input (the SmallThinker layer) in Flax.

The sixth encoder stack, and the third sparse one beside ``longcat.py`` and
``pangu_moe.py``: a pre-norm causal decoder with no dense FFN and no shared
expert — every layer is grouped-query attention then routed ReLU-gated
experts — in which layer ``i`` is *global with no positional encoding* where
``rope_layout[i]`` / ``sliding_window_layout[i]`` are 0 and *windowed with
RoPE* where they are 1 (published: one global layer, then three windowed, 13
times), and in which the router's logits come from the **attention's input**,
so the routing is known before attention has run. Config keys are the
published ``config.json``'s (``SmallThinkerConfig.from_hf_dict`` reads one
directly).

Layer input ``x`` [tokens, hidden]; RMSNorm ``N`` (learned scale); no biases::

    n = N_in(x)
    l = n W_r                          [experts], float32      the router reads the attention's INPUT
    c = top-k of l ; g = softmax(l[c])                         moe_primary_router_apply_softmax, norm_topk_prob
    q, k, v = n W_q [heads x d], n W_k [kv heads x d], n W_v [kv heads x d]
    rope_layout[i] = 1: q, k rotated (rotate-half over all d, theta, positions of the real tokens)
    sliding_window_layout[i] = 1: key j visible to query t iff t - window < j <= t ; else iff j <= t
    a = x + softmax(q k^T / sqrt(d) + mask) v W_o              heads / kv heads query heads a key/value head
    m = N_post(a)
    y = a + sum over e in c of g_e ( relu(m W_gate_e) * (m W_up_e) ) W_down_e

**Which experts are mine** is ``longcat.py``'s statement: ``experts_held =
(lo, hi)`` is this chip's range of the router's outputs; the router keeps its
width and its k, the softmax is over all k chosen, held or not, and what absent
experts would add is left out. Held whole (``None``, the published model's 64
on one chip) nothing is absent, and ``ops/grouped.held_expert_ffn`` puts the
experts' rows back onto their tokens by a gather through the sort's inverse.

Under left padding everything starts at a row's first real token: a pad is
routed nowhere (``mask_pads``), is no key to anyone, and RoPE's positions count
real tokens — so a row's real tokens read what the row alone would.

Precision as the other decoders': weights and activations ``dtype``, products
accumulate in float32; RMSNorm, softmax and the experts' gated product in
float32; the router's product (``Precision.HIGHEST``), top-k and softmax in
float32 from a float32 kernel. Attention is one kernel where it can run
(``ops/gqa_attention.py``: one TPU device, heads of 128, whole 128-row tiles:
keys streamed by tile, tiles outside the band or wholly pad never visited)
and computed in blocks over the queries elsewhere
(``ops/ring_attention.blocked_causal_attention``). ``tie_word_embeddings`` false: an encoder that
hands out final-norm states builds no head. The config names no *secondary*
experts and none is built.

``stats`` (read by the joint trainer where it reads the loss): ``moe`` — the
routing counts ``layers.sow_and_count`` gives, summed over layers, plus
``gathered``, the held assignments the gather combined, and ``gather_slots``,
the token rows that gather visited times ``k`` (``ops/grouped.gather_slots``:
``assigned`` over it is how full the visited chunks were) — and ``attn`` —
``layers``, ``window_layers``, ``fused`` (the layers whose attention ran the
kernel: all or none), ``pairs_needed`` (a head's real query-key pairs inside
causal and band, from the pad mask) and ``pairs_computed`` (those the path
that ran multiplied: the kernel's visited tiles or the blocks of
``blocked_causal_attention``), float32 (a step's pairs pass 2^31 at four rows
of 8,192). The scopes ``layers_i/router``, ``layers_i/attn/scores``,
``layers_i/moe/held_experts`` (and ``combine`` under it) are what
``benchmark/tools/program_trace.py`` sums.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from deepdfa_tpu.llm.layers import (
    HeldRange,
    RMSNorm,
    apply_rope,
    embed_tokens,
    mask_pads,
    proj,
    rope_cos_sin,
    sow_and_count,
    sow_stats,
)
from deepdfa_tpu.llm.longcat import held_experts
from deepdfa_tpu.ops.dispatch import kernel_mode
from deepdfa_tpu.ops.grouped import gather_slots
from deepdfa_tpu.ops.ring_attention import blocked_causal_attention, blocked_key_ranges

__all__ = ["SmallThinkerConfig", "SmallThinkerModel", "smallthinker_21b", "tiny_smallthinker",
           "route", "needed_pairs", "computed_pairs"]

_PERIOD = (0, 1, 1, 1)  # one global layer without RoPE, three windowed with it


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig(HeldRange):
    """Published ``config.json`` keys (defaults: SmallThinker-21BA3B-Instruct)
    plus the TPU-side knobs at the end."""

    vocab_size: int = 151936
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_ffn_hidden_size: int = 768
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    rope_layout: tuple[int, ...] = _PERIOD * 13  # by layer: 1 = q, k rotated
    sliding_window_layout: tuple[int, ...] = _PERIOD * 13  # by layer: 1 = the window
    sliding_window_size: int = 4096
    rope_theta: float = 1_500_000.0
    rope_scaling: None = None
    max_position_embeddings: int = 16384
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    # [lo, hi) of the experts held here; None = all of them
    experts_held: tuple[int, int] | None = None
    attn_block_q: int = 512  # queries per attention block
    # sorted assignments a trip of the expert loop takes (ops/grouped.py)
    moe_chunk_rows: int = 4096

    def __post_init__(self):
        self._check_held()
        for name in ("rope_layout", "sliding_window_layout"):
            layout = tuple(int(v) for v in getattr(self, name))
            if len(layout) < self.num_hidden_layers or set(layout) - {0, 1}:
                raise ValueError(f"{name} {layout} does not say 0 or 1 for each of the "
                                 f"{self.num_hidden_layers} layers")
            object.__setattr__(self, name, layout)
        if not (self.moe_primary_router_apply_softmax and self.norm_topk_prob):
            raise ValueError("moe_primary_router_apply_softmax=False or norm_topk_prob=False is "
                             "another router: none is written here")
        if self.rope_scaling is not None:
            raise ValueError("rope_scaling is not built here: the published value is null")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(f"{self.num_attention_heads} query heads do not share "
                             f"{self.num_key_value_heads} key/value heads evenly")

    @property
    def n_routed_experts(self) -> int:  # ``HeldRange``'s and the drivers' name for it
        return self.moe_num_primary_experts

    def window(self, layer: int) -> int | None:
        """``layer``'s window, ``None`` for a global layer."""
        return self.sliding_window_size if self.sliding_window_layout[layer] else None

    @property
    def window_layers(self) -> int:
        return sum(self.sliding_window_layout[:self.num_hidden_layers])


def smallthinker_21b(**kw) -> SmallThinkerConfig:
    """PowerInfer/SmallThinker-21BA3B-Instruct, as published."""
    return SmallThinkerConfig(**kw)


def tiny_smallthinker(**kw) -> SmallThinkerConfig:
    """Test-size config (CI): two periods of the pattern (global at layers 0
    and 4 of 8), 4 query heads over 2 key/value heads, 8 experts top-3, a
    window of 24 positions, attention in blocks of 16."""
    defaults = dict(
        vocab_size=320, hidden_size=64, num_hidden_layers=8, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, moe_ffn_hidden_size=32, moe_num_primary_experts=8,
        moe_num_active_primary_experts=3, rope_layout=_PERIOD * 2,
        sliding_window_layout=_PERIOD * 2, sliding_window_size=24, max_position_embeddings=256,
        dtype="float32", attn_block_q=16, moe_chunk_rows=32,
    )
    defaults.update(kw)
    return SmallThinkerConfig(**defaults)


def route(n: jnp.ndarray, w_r: jnp.ndarray, cfg: SmallThinkerConfig):
    """``(choice [t, k] int32, gates [t, k] float32)`` over all experts: the
    top k of the logits, then a softmax over the k chosen (the softmax over
    all of them renormalised over the chosen is the same numbers). Product and
    softmax float32."""
    logits = jnp.dot(n.astype(jnp.float32), w_r, precision=lax.Precision.HIGHEST)
    top, choice = lax.top_k(logits, cfg.moe_num_active_primary_experts)
    return choice.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


def _fused_attention(cfg: SmallThinkerConfig, seq_len: int) -> bool | None:
    """The ``interpret`` flag for the grouped-query attention kernel, or
    ``None`` where ``blocked_causal_attention`` has to run (``ops/dispatch.py``)."""
    return kernel_mode("gqa_attention", seq_len, cfg.num_attention_heads,
                       cfg.num_key_value_heads, cfg.head_dim)


def computed_pairs(cfg: SmallThinkerConfig, mask: jnp.ndarray, window: int | None) -> jnp.ndarray:
    """Query-key pairs a head of one attention layer *multiplies* over the
    rows of ``mask`` [b, s], by the path that runs: the kernel's visited tiles
    (fewer under left padding) or the blocks of ``blocked_causal_attention``.
    float32."""
    b, s = mask.shape
    if _fused_attention(cfg, s) is not None:
        from deepdfa_tpu.ops.gqa_attention import default_tile, visited_pairs

        return visited_pairs(mask, default_tile(s), default_tile(s), window)
    return jnp.float32(b * sum((end - start) * (end - lo) for start, end, lo in
                               blocked_key_ranges(s, cfg.attn_block_q, window)))


def needed_pairs(mask: jnp.ndarray, window: int | None) -> jnp.ndarray:
    """Query-key pairs one attention layer *needs* over the rows of ``mask``
    [b, s]: for each real query the real keys at or before it, inside the
    ``window`` if there is one. Summed in whole numbers (int32: exact up to 64
    rows of 8,192), handed out as float32."""
    seen = jnp.cumsum(mask.astype(jnp.int32), axis=-1)  # real keys at or before each position
    if window is not None and window < mask.shape[1]:
        seen = seen - jnp.pad(seen, ((0, 0), (window, 0)))[:, :mask.shape[1]]
    return jnp.sum(jnp.where(mask, seen, 0)).astype(jnp.float32)


class GroupedQueryAttention(nn.Module):
    """Causal attention whose query heads share ``num_key_value_heads``
    key/value heads; rotated where ``rope``, banded where ``window``."""

    cfg: SmallThinkerConfig
    rope: bool
    window: int | None

    @nn.compact
    def __call__(self, x, mask, positions):
        cfg = self.cfg
        h, hk, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        b, s, _ = x.shape
        q = proj(h * d, ("embed", "heads"), cfg, "q_proj")(x).reshape(b, s, h, d)
        k = proj(hk * d, ("embed", "kv_heads"), cfg, "k_proj")(x).reshape(b, s, hk, d)
        v = proj(hk * d, ("embed", "kv_heads"), cfg, "v_proj")(x).reshape(b, s, hk, d)
        if self.rope:
            cos, sin = rope_cos_sin(positions, d, cfg.rope_theta)
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        fused = _fused_attention(cfg, s)
        with jax.named_scope("scores"):
            if fused is not None:
                from deepdfa_tpu.ops.gqa_attention import gqa_attention

                # heads side by side, as the projections give and take them
                out = gqa_attention(
                    q.reshape(b, s, h * d), k.reshape(b, s, hk * d), v.reshape(b, s, hk * d),
                    mask, num_kv_heads=hk, window=self.window, interpret=fused)
            else:
                out = blocked_causal_attention(
                    q, k, v, kv_mask=mask, block_q=cfg.attn_block_q,
                    window=self.window).reshape(b, s, h * d)
        return proj(cfg.hidden_size, ("heads", "embed"), cfg, "o_proj")(out)


class ExpertLayer(nn.Module):
    """The router over all experts, read from ``n``; the held experts' part of
    the result for ``m`` (module docstring)."""

    cfg: SmallThinkerConfig

    @nn.compact
    def __call__(self, n, m, token_mask):
        cfg = self.cfg
        b, s, d = m.shape
        w_r = self.param(
            "router_kernel",
            nn.with_logical_partitioning(nn.initializers.lecun_normal(), ("embed", "router")),
            (d, cfg.moe_num_primary_experts), jnp.float32)
        with jax.named_scope("router"):
            choice, gates = mask_pads(*route(n.reshape(b * s, d), w_r, cfg), token_mask)
        out, computed = held_experts(
            self, m.reshape(b * s, d), choice, gates, cfg.moe_ffn_hidden_size,
            activation=jax.nn.relu)
        counts = sow_and_count(self, choice, computed, (b, s))
        counts["gathered"] = counts["held"] * cfg.holds_every_expert
        counts["gather_slots"] = gather_slots(choice) * cfg.holds_every_expert
        return out.astype(jnp.dtype(cfg.dtype)).reshape(b, s, d), counts


class SmallThinkerLayer(nn.Module):
    """Attention — global, or windowed with RoPE — then the experts, each
    behind a norm; the router reads the first norm's output."""

    cfg: SmallThinkerConfig
    rope: bool
    window: int | None

    @nn.compact
    def __call__(self, x, mask, positions):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dtype=dtype, name=name)
        n = norm("input_norm")(x)
        a = x + GroupedQueryAttention(cfg, self.rope, self.window, name="attn")(n, mask, positions)
        y, counts = ExpertLayer(cfg, name="moe")(n, norm("post_attn_norm")(a), mask)
        return nn.with_logical_constraint(a + y, ("batch", "seq", "embed")), counts


class SmallThinkerModel(nn.Module):
    """Decoder stack -> final-norm hidden states [b, s, hidden], the joint
    trainer's encoder contract (``llm.apply(params, input_ids, pad_mask)``)."""

    cfg: SmallThinkerConfig

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
        totals = None
        for i in range(cfg.num_hidden_layers):
            x, counts = SmallThinkerLayer(
                cfg, bool(cfg.rope_layout[i]), cfg.window(i), name=f"layers_{i}")(
                    x, attn_mask, positions)
            totals = counts if totals is None else jax.tree.map(jnp.add, totals, counts)
        sow_stats(self, "moe", totals)  # the other decoders' collections: summed over layers
        n_win = cfg.window_layers
        n_glob = cfg.num_hidden_layers - n_win
        fused = _fused_attention(cfg, s) is not None  # every layer's attention or none's
        by_kind = lambda pairs: n_glob * pairs(None) + n_win * pairs(cfg.sliding_window_size)
        sow_stats(self, "attn", {
            "layers": jnp.int32(cfg.num_hidden_layers), "window_layers": jnp.int32(n_win),
            "fused": jnp.int32(cfg.num_hidden_layers * fused),
            "pairs_needed": by_kind(lambda w: needed_pairs(attn_mask, w)),
            "pairs_computed": by_kind(lambda w: computed_pairs(cfg, attn_mask, w)),
        })
        return RMSNorm(cfg.rms_norm_eps, dtype=jnp.dtype(cfg.dtype), name="norm")(x)
