"""The Mamba-1 selective scan and the depthwise causal convolution before it.

The one op family of the repo that carries state *along* the sequence: every
other mixer (``flash_attention``, ``latent_attention``, ``ring_attention``) is
a product over all pairs of positions. Per channel ``d`` and state ``n``::

    c_t = silu(sum_j w_j * u_{t-(k-1)+j} + b)                 causal_conv1d, u before position 0 is 0
    s_t = exp(delta_t * A[d, n]) * s_{t-1} + delta_t * c_t * B_t[n]        s_{-1} = 0
    y_t = sum_n s_t[d, n] * C_t[n] + D[d] * c_t               selective_scan

**Padding is a correctness matter here, not only waste.** The joint trainer
pads on the left, so a recurrence meets the pads *first*: an unmasked
convolution window reaches back into them and an unmasked state integrates
them, and a row's result would depend on how much padding its batch gave it.
``causal_conv1d`` zeroes the pads before the taps and after the activation,
``selective_scan`` zeroes what a pad would add to the state: ``delta * c`` is
0 there and a state of 0 decays to 0, so the state is *exactly* 0 at the first
real token and a left-padded row's real tokens read what the row alone would.

Plain ``jax`` (no kernel yet: ``fused`` in the model's ``ssm`` counts is 0):
``lax.scan`` over the positions carrying the ``[b, n, d]`` float32 state
(channels on the lanes), :data:`UNROLL` positions a trip. The state and its
whole recurrence are float32 whatever the inputs are, and no ``[b, s, d, n]``
array of the whole sequence is ever built. The form and the trip were chosen
on the chip at ``[4, 2048, 5120]`` x 16 states (PERF.md section 6): 17.4 /
9.3 / 7.6 / 8.2 / 6.7 / 6.4 ms a layer at 1 / 2 / 4 / 8 / 16 / 32 positions a
trip (XLA keeps the state in fast memory across a trip's positions); chunks
of 32-256 positions done in parallel by ``lax.associative_scan`` over the
pairs ``(exp(delta A), delta c B)`` took 122-177 ms (they move ``[b, chunk,
16, 5120]`` float32 arrays through HBM several times a chunk) and are not
kept.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["causal_conv1d", "selective_scan", "UNROLL"]

UNROLL = 32  # positions a trip of the loop


def causal_conv1d(u: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                  mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """``silu`` of the depthwise causal convolution, pads zeroed on both sides
    of it. u: [b, s, d]; w: [k, d] (``w[k - 1]`` is the tap on ``u_t``); b:
    [d]; mask: [b, s] (True = a real token). float32 inside, ``u``'s dtype out."""
    k, s = w.shape[0], u.shape[1]
    real = None if mask is None else mask[..., None]
    uf = u.astype(jnp.float32)
    if real is not None:
        uf = jnp.where(real, uf, 0.0)
    padded = jnp.pad(uf, ((0, 0), (k - 1, 0), (0, 0)))
    wf = w.astype(jnp.float32)
    acc = jnp.broadcast_to(b.astype(jnp.float32), uf.shape)
    for j in range(k):  # k shifted products: u_{t-(k-1)+j} is padded[t + j]
        acc = acc + wf[j] * padded[:, j:j + s]
    c = jax.nn.silu(acc)
    if real is not None:
        c = jnp.where(real, c, 0.0)
    return c.astype(u.dtype)


def _step(a_t, state, dt, x, b_t, c_t):
    """One position: ``a_t`` [n, d], ``state`` [b, n, d], ``dt`` / ``x`` [b, d],
    ``b_t`` / ``c_t`` [b, n] -> (state, y [b, d])."""
    state = jnp.exp(dt[:, None, :] * a_t) * state + x[:, None, :] * b_t[:, :, None]
    return state, jnp.sum(state * c_t[:, :, None], axis=1)


def selective_scan(c: jnp.ndarray, delta: jnp.ndarray, A: jnp.ndarray, B: jnp.ndarray,
                   C: jnp.ndarray, D: jnp.ndarray, mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """``y`` [b, s, d] of the recurrence in the module docstring. c: [b, s, d]
    (the convolution's output); delta: [b, s, d] (after its softplus); A: [d,
    n] (negative); B, C: [b, s, n]; D: [d]; mask: [b, s] (True = a real
    token; a pad adds nothing to the state). float32 inside, ``c``'s dtype out."""
    f32 = lambda v: v.astype(jnp.float32)
    time_major = lambda v: jnp.swapaxes(f32(v), 0, 1)
    cf, delta = f32(c), f32(delta)
    x = delta * cf
    if mask is not None:
        x = jnp.where(mask[..., None], x, 0.0)
    a_t = f32(A).T  # [n, d]: channels on the lanes
    state = jnp.zeros((c.shape[0], *a_t.shape), jnp.float32)
    _, ys = lax.scan(lambda s, inp: _step(a_t, s, *inp), state,
                     tuple(map(time_major, (delta, x, B, C))), unroll=UNROLL)
    return (time_major(ys) + f32(D) * cf).astype(c.dtype)
