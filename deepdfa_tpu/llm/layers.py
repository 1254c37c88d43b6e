"""What the decoders share, in no family's file: the pieces a decoder of any
family builds from, read from its config by the published names the
families have in common (``hidden_size``, ``vocab_size``, ``dtype``, and for
an expert layer ``experts_held``, ``n_routed_experts``, ``moe_chunk_rows``).

- :func:`proj`, :class:`DenseFFN`, :class:`RMSNorm`, :func:`rope_cos_sin` /
  :func:`apply_rope` (rotate-half RoPE) and :func:`embed_tokens`: the
  layers, as the model files call them;
- :class:`HeldRange` (the configs' ``experts_held`` half), :func:`mask_pads`
  and :func:`sow_and_count`: what an expert layer does once its router has
  chosen, beside ``longcat.held_experts``;
- :func:`sow_stats`: the one form of the ``stats`` channel, the counts a
  step hands the joint trainer where it reads the loss;
- :func:`dense_chunks` and :func:`over_live`: a layer's position-wise halves
  over the chunks of positions that hold a real token (a decoder passes its
  own chunk width).

Which kernel an op runs is not decided here but in ``ops/dispatch.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepdfa_tpu.ops.grouped import combined_positions

__all__ = [
    "proj",
    "DenseFFN",
    "RMSNorm",
    "rope_cos_sin",
    "apply_rope",
    "embed_tokens",
    "HeldRange",
    "mask_pads",
    "sow_and_count",
    "sow_stats",
    "dense_chunks",
    "over_live",
]


def proj(features: int, axes: tuple, cfg, name: str) -> nn.Dense:
    """A projection with no bias, weights and activations at ``cfg.dtype``,
    its kernel under the logical ``axes``."""
    dtype = jnp.dtype(cfg.dtype)
    return nn.Dense(
        features, use_bias=False, dtype=dtype, param_dtype=dtype,
        kernel_init=nn.with_logical_partitioning(nn.initializers.lecun_normal(), axes),
        name=name,
    )


class DenseFFN(nn.Module):
    """``W_down(silu(W_gate x) * (W_up x))``, ``width`` wide: a dense FFN, or
    an expert every token passes through."""

    cfg: Any
    width: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gate = proj(self.width, ("embed", "mlp"), cfg, "gate_proj")(x)
        up = proj(self.width, ("embed", "mlp"), cfg, "up_proj")(x)
        return proj(cfg.hidden_size, ("mlp", "embed"), cfg, "down_proj")(nn.silu(gate) * up)


class RMSNorm(nn.Module):
    """LLaMA RMSNorm: fp32 variance, learned scale (HF ``LlamaRMSNorm``)."""

    eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        w = self.param(
            "weight",
            nn.with_logical_partitioning(nn.initializers.ones, ("norm",)),
            (x.shape[-1],),
        )
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + self.eps)
        return (w * y.astype(self.dtype)).astype(self.dtype)


def rope_cos_sin(
    positions: jnp.ndarray, head_dim: int, theta: float
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Rotary tables for integer ``positions`` [..., s] -> cos/sin [..., s, d/2]."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(
    x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray
) -> jnp.ndarray:
    """HF llama rotary convention: rotate_half over a [d/2, d/2] split.

    x: [b, s, h, d]; cos/sin: [b, s, d/2] (or broadcastable).
    """
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def embed_tokens(cfg, input_ids):
    """The decoder's embedding of ``input_ids`` (the calling model's
    ``embed_tokens`` submodule), [b, s, hidden]."""
    dtype = jnp.dtype(cfg.dtype)
    x = nn.Embed(
        cfg.vocab_size, cfg.hidden_size, dtype=dtype, param_dtype=dtype,
        embedding_init=nn.with_logical_partitioning(
            nn.initializers.normal(0.02), ("vocab", "embed")),
        name="embed_tokens",
    )(input_ids)
    return nn.with_logical_constraint(x, ("batch", "seq", "embed"))


class HeldRange:
    """What the sparse decoders' configs do alike (a mixin of their frozen
    dataclasses): ``experts_held`` checked and as a range, and a config read
    from a published ``config.json``'s keys."""

    def _check_held(self):
        if self.experts_held is not None:
            lo, hi = self.experts_held
            if not 0 <= lo < hi <= self.n_routed_experts:
                raise ValueError(f"experts_held {self.experts_held} is no range of the "
                                 f"{self.n_routed_experts} routed experts")
            object.__setattr__(self, "experts_held", (int(lo), int(hi)))

    @property
    def held(self) -> tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def holds_every_expert(self) -> bool:
        """No choice of the router's is absent or zero-compute: the held range
        is its whole width, so a real token's k assignments are all here (the
        case ``held_expert_ffn`` combines by a gather)."""
        return self.held == (0, getattr(self, "router_width", self.n_routed_experts))

    @classmethod
    def from_hf_dict(cls, d: dict):
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in names}
        if kw.get("experts_held") is not None:
            kw["experts_held"] = tuple(kw["experts_held"])
        return cls(**kw)


def mask_pads(choice, gates, token_mask):
    """A pad token is routed nowhere: its choices -1, its gates 0."""
    if token_mask is None:
        return choice, gates
    real = token_mask.reshape(-1, 1)
    return jnp.where(real, choice, -1), jnp.where(real, gates, 0.0)


def sow_and_count(layer: nn.Module, choice, computed, batch_shape: tuple, zero=None) -> dict:
    """Sow this layer's choices ([b, s, k]; -1: a pad) into ``routing`` and
    return its assignments by where they went: ``load_max`` the fullest held
    expert's; ``combined`` the sorted positions the one-hot combine of
    ``held_expert_ffn`` visited for them (``ops/grouped.py``; ``held`` over it
    is how full its blocks were; 0 where the gather combines them);
    ``slots`` and ``layers`` make means of sums.
    ``zero`` marks the choices that went to zero-compute experts (none where a
    router has none)."""
    lo, hi = layer.cfg.held
    n = hi - lo
    layer.sow("routing", "choice", choice.reshape(*batch_shape, choice.shape[-1]))
    if zero is None:
        zero = jnp.zeros(choice.shape, bool)
    held = (choice >= lo) & (choice < hi)
    n_held = jnp.sum(held, dtype=jnp.int32)
    load = jnp.sum((choice[..., None] - lo) == jnp.arange(n), axis=(0, 1), dtype=jnp.int32)
    return {
        "assigned": jnp.sum(choice >= 0, dtype=jnp.int32),
        "held": n_held,
        "zero": jnp.sum(zero, dtype=jnp.int32),
        "absent": jnp.sum((choice >= 0) & ~held & ~zero, dtype=jnp.int32),
        "load_max": jnp.max(load),
        "dropped": n_held - computed,
        "combined": (jnp.int32(0) if layer.cfg.holds_every_expert
                     else combined_positions(n_held, layer.cfg.moe_chunk_rows)),
        "slots": jnp.int32(n),
        "layers": jnp.int32(1),
    }


def sow_stats(module: nn.Module, name: str, counts: dict) -> None:
    """``counts`` into ``module``'s ``stats`` collection under ``name``, for
    whoever applies the model with ``mutable=["stats"]`` (the joint step:
    onto ``loss.sync``): one step's, replaced, not appended, on each apply."""
    module.sow("stats", name, counts, reduce_fn=lambda _, new: new, init_fn=dict)


def dense_chunks(mask: jnp.ndarray, block: int):
    """``(live [chunks] bool, width)``: a layer's position-wise halves walk
    the ``b * s`` positions of ``mask`` [b, s] in chunks of ``width`` —
    ``block`` where it divides ``s``, else the whole row — and a chunk is
    live where it holds a real token."""
    b, s = mask.shape
    width = block if s % block == 0 else s
    return jnp.any(mask.reshape(b * s // width, width), axis=1), width


def over_live(half, skip, live, *chunks):
    """``half(*c)`` for the chunks ``c`` of ``chunks`` (each [n, ...], chunk
    by chunk on the leading axis) where ``live``, ``skip(*c)`` elsewhere,
    stacked on the leading axis. The stack starts as ``skip`` of every chunk;
    one loop over the chunks writes ``half``'s result over a live chunk's in
    place (a ``lax.cond`` a trip), so the product that makes a chunk writes it
    straight into the stack (a scan stacking each trip's output copies it
    there in a pass of its own)."""
    def trip(i, out):
        def write(out):
            new = half(*(c[i] for c in chunks))
            return jax.tree.map(lambda o, v: jax.lax.dynamic_update_index_in_dim(o, v, i, 0),
                                out, new)

        return jax.lax.cond(live[i], write, lambda o: o, out)

    return jax.lax.fori_loop(0, live.shape[0], trip, jax.vmap(skip)(*chunks))
