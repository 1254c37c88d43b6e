"""Compressed convolutional attention's prologue (CCA; Figliolia et al.,
Zyphra 2025, arXiv:2510.04476): what turns the latent queries, keys and
values of one layer into the operands of a causal softmax attention. Plain
``jax.numpy``; the attention itself is ``ops/gqa_attention.py``'s kernel or
``ops/ring_attention.blocked_causal_attention``, by ``ops/dispatch.py``.

For ``q~`` [b, s, heads x d] and ``k~`` [b, s, kv heads x d] (one product
each from the layer's normed input), ``v`` [b, s, 2 x d] (``v_proj``), the
pad mask [b, s] and query head ``h`` reading key/value head ``g(h) = h //
(heads / kv heads)``::

    u    = [q~ ; k~]                                   pads zeroed
    u1_t = w1[0] * u_t + w1[1] * u_{t-1}               depthwise_conv: a causal kernel of 2
    u2_t = u1_t W2[0, head] + u1_{t-1} W2[1, head]     grouped_conv: [d, d] a head a tap
    q_h  = u2_q,h + (q~_h + k~_g(h)) / 2               qk_mean
    k_g  = u2_k,g + (k~_g + mean over g(h) = g of q~_h) / 2
    q^_h = q_h / |q_h| * sqrt(d) ; k^_g = k_g / |k_g| * tau_g        l2_temperature
    q^, k^: dims [0, rot) turned by rotate-half, the rest left          partial_rope
    v    = [v_t, head 0 ; v_{t-1}, head 1]             value_shift

Scores ``q^_h . k^_g / sqrt(d)`` are then the cosine of the two times the
key head's learned temperature. **Every sequence op starts at a row's first
real token**: the pads are zeroed before each convolution and before the
value shift, so ``x_{t-1}`` at a row's first real token is 0, and RoPE's
positions count real tokens. Under left padding a row's real tokens so read
what the row alone would.

float32 inside (the grouped product takes ``dtype`` operands and sums in
float32, as the layer's projections do), ``dtype`` out. Each step is a
module global read at call time (``benchmark/tools/prove_frozen_zaya.py``
plants faults by replacing them).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

__all__ = ["shift", "depthwise_conv", "grouped_conv", "qk_mean", "l2_temperature",
           "partial_rope", "value_shift", "prologue"]

_TINY = 1e-24  # under |x|^2 of a zeroed pad: its direction is 0, not NaN


def _zero_pads(x, mask):
    return jnp.where(mask.reshape(mask.shape + (1,) * (x.ndim - 2)), x, 0)


def shift(x, mask):
    """``x_{t-1}`` along axis 1 of ``x`` [b, s, ...], pads zeroed first: 0 at
    position 0 and at a row's first real token."""
    x = _zero_pads(x, mask)
    return jnp.pad(x, ((0, 0), (1, 0)) + ((0, 0),) * (x.ndim - 2))[:, :-1]


def depthwise_conv(u, w, mask):
    """``w[0] * u_t + w[1] * u_{t-1}``, channel by channel: u [b, s, c], w [2, c]."""
    return w[0] * _zero_pads(u, mask) + w[1] * shift(u, mask)


def grouped_conv(u, w, mask, dtype):
    """``u_t W[0, g] + u_{t-1} W[1, g]`` within each head ``g``: u [b, s, g, d]
    float32, w [2, g, d, d]; operands ``dtype``, sums float32."""
    tap = lambda x, wk: jnp.einsum("bsgd,gde->bsge", x.astype(dtype), wk.astype(dtype),
                                   preferred_element_type=jnp.float32)
    return tap(_zero_pads(u, mask), w[0]) + tap(shift(u, mask), w[1])


def qk_mean(q2, k2, q0, k0):
    """Each query head plus the mean of its pre-convolution self and its key
    head's; each key head plus the mean of its pre-convolution self and its
    query heads' mean. q [b, s, h, d], k [b, s, hk, d]."""
    b, s, h, d = q0.shape
    hk = k0.shape[2]
    q_of_k = q0.reshape(b, s, hk, h // hk, d).mean(3)
    return q2 + (q0 + jnp.repeat(k0, h // hk, axis=2)) / 2, k2 + (k0 + q_of_k) / 2


def l2_temperature(q, k, tau):
    """``q / |q| * sqrt(d)`` and ``k / |k| * tau`` over each head's ``d``."""
    unit = lambda x: x * lax.rsqrt(jnp.maximum(jnp.sum(x * x, -1, keepdims=True), _TINY))
    return unit(q) * jnp.sqrt(jnp.float32(q.shape[-1])), unit(k) * tau.astype(jnp.float32)[:, None]


def partial_rope(x, cos, sin):
    """Rotate-half over the first ``rot = 2 * cos.shape[-1]`` dims of each
    head of ``x`` [b, s, h, d] (``(x1, x2)`` the two halves of those ``rot``,
    cos / sin [b, s, rot / 2]); the rest as they are."""
    half = cos.shape[-1]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    cos, sin = cos[:, :, None], sin[:, :, None]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def value_shift(v, mask):
    """Value head 0 reads its own token, head 1 the previous one: v [b, s, 2, d]."""
    return jnp.stack([_zero_pads(v[:, :, 0], mask), shift(v[:, :, 1], mask)], axis=2)


def prologue(q0, k0, v, w1, w2, tau, mask, cos, sin, *, heads: int, dtype):
    """``(q^, k^, v)`` as the attention takes them, heads apart ([b, s, heads
    | kv heads | 2, d], ``dtype``) from the latent ``q0`` [b, s, heads x d],
    ``k0`` [b, s, kv heads x d] and ``v`` [b, s, 2 x d] (module docstring)."""
    b, s, _ = q0.shape
    d = q0.shape[-1] // heads
    hk = k0.shape[-1] // d
    u = jnp.concatenate([q0, k0], axis=-1).astype(jnp.float32)
    u2 = grouped_conv(depthwise_conv(u, w1.astype(jnp.float32), mask).reshape(b, s, -1, d),
                      w2, mask, dtype)
    heads_of = lambda x, n: _zero_pads(x, mask).reshape(b, s, n, d)
    q, k = qk_mean(u2[:, :, :heads], u2[:, :, heads:], heads_of(u[..., :heads * d], heads),
                   heads_of(u[..., heads * d:], hk))
    q, k = l2_temperature(q, k, tau)
    q, k = partial_rope(q, cos, sin), partial_rope(k, cos, sin)
    v = value_shift(v.reshape(b, s, -1, d), mask)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype)
