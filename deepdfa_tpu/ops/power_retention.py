"""Degree-2 power retention: gated linear attention whose state is the
symmetric tensor power of the keys (Manifest AI's power attention, "Scaling
Context Requires Rethinking Attention", arXiv 2507.04239).

Per key/value head, with ``log g`` the layer's log-gates (``<= 0``) and ``j``
running over the real tokens at or before ``t``::

    w_tj = (q_t . k_j / sqrt(d))^2 * exp(sum_{j<u<=t} log g_u)
    o_t  = sum_j w_tj v_j / (sum_j w_tj + eps)

**Why it is linear in length.** ``phi(x)`` holds ``x_a x_b`` for ``a <= b``,
scaled by ``sqrt 2`` where ``a < b``, so ``phi(q) . phi(k) = (q . k)^2``: its
width is ``D = d (d + 1) / 2`` (8,256 at ``d = 128``). The recurrence
``S_t = g_t S_{t-1} + phi(k_t) v_t^T``, ``z_t = g_t z_{t-1} + phi(k_t)`` gives
``o_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)`` with a state of ``[D, d]``
whatever the length.

**The chunked form** (:func:`power_retention_plain`, the CPU's path and the
kernel's fallback) cuts a row into chunks of ``C`` positions with ``G`` the
log-gates summed from the chunk's start::

    o = [ ((Q K^T / sqrt d)^2 * exp(G_t - G_j) * causal * real_j) [V | 1]
          + exp(G_t) phi(Q) [S | z] ]   then numerator / (denominator + eps)
    [S | z] <- exp(G_C) [S | z] + phi(K)^T (exp(G_C - G_j) * real_j [V | 1])

**Pads.** A pad adds nothing to ``S`` or ``z`` (its weight is 0) and decays
nothing (its log-gate is taken as 0), so a left-padded row's real tokens read
what the row alone would; what a pad query reads is finite and unread.

**Two forms.** On one TPU device, at a shape :func:`supports` takes, the op is
one Pallas kernel (``ops/power_retention_kernel.py``, imported where it first
runs) whose float32 state stays on the chip over a row's chunks and which
never visits a chunk of leading pads; elsewhere it is the plain form here. The
decoder that uses it is frozen: neither form has a backward, and
:func:`power_retention` raises where it is differentiated.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["power_retention", "power_retention_plain", "phi", "supports", "chunks_needed",
           "chunks_computed", "EPS"]

EPS = 1e-6  # the normaliser's (the configuration file's ``assumed``)
HEAD_DIM = 128  # the kernel's head width (one lane tile)


@functools.cache
def _pairs(d: int):
    a, b = np.triu_indices(d)
    return a, b, np.where(a == b, 1.0, math.sqrt(2.0)).astype(np.float32)


def phi(x: jnp.ndarray) -> jnp.ndarray:
    """``[..., d] -> [..., d (d + 1) / 2]`` float32: ``x_a x_b`` for ``a <=
    b``, ``sqrt 2`` off the diagonal, so that ``phi(q) . phi(k) = (q . k)^2``."""
    a, b, coef = _pairs(x.shape[-1])
    x = x.astype(jnp.float32)
    return x[..., a] * x[..., b] * coef


def supports(seq_len: int, num_heads: int, num_kv_heads: int, head_dim: int, chunk: int) -> bool:
    """Whether the kernel takes this shape: heads of 128 (one lane tile), query
    heads in whole groups of their key/value head, whole chunks of whole
    sublane tiles."""
    return (head_dim == HEAD_DIM and num_heads % num_kv_heads == 0 and chunk % 8 == 0
            and seq_len % chunk == 0)


def chunks_needed(mask: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """Chunks of ``chunk`` positions that hold a real token, over the rows of
    ``mask`` [b, s]. int32."""
    b, s = mask.shape
    return jnp.sum(jnp.any(mask.reshape(b, s // chunk, chunk), axis=-1)).astype(jnp.int32)


def chunks_computed(mask: jnp.ndarray, chunk: int, fused: bool) -> jnp.ndarray:
    """Chunks the path that runs visits over the rows of ``mask`` [b, s]: the
    kernel's, from the one that holds a row's first real token to the row's
    end (none for a row of pads alone); the plain form's, all of them. int32."""
    b, s = mask.shape
    n = s // chunk
    if not fused:
        return jnp.int32(b * n)
    first = jnp.where(mask.any(axis=1), jnp.argmax(mask, axis=1), s)
    return jnp.sum(jnp.maximum(n - first // chunk, 0)).astype(jnp.int32)


def _weights(scores: jnp.ndarray) -> jnp.ndarray:
    """A pair's weight from its score ``q . k / sqrt d``: the degree-2 power."""
    return jnp.square(scores)


def _ratio(num: jnp.ndarray, den: jnp.ndarray, eps: float) -> jnp.ndarray:
    """The normalised read: the weighted values over the weights' sum."""
    return num / (den + eps)


def _chunk_gates(log_g: jnp.ndarray, mask: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """``G`` [b, s, hk] float32: the log-gates summed from each chunk's start,
    0 at a pad (a pad decays nothing)."""
    b, s, hk = log_g.shape
    lg = jnp.where(mask[..., None], log_g.astype(jnp.float32), 0.0)
    return jnp.cumsum(lg.reshape(b, s // chunk, chunk, hk), axis=2).reshape(b, s, hk)


def power_retention_plain(q, k, v, log_g, mask=None, *, chunk: int) -> jnp.ndarray:
    """``o`` [b, s, h, d] of the module docstring by the chunked form. q: [b,
    s, h, d]; k, v: [b, s, hk, d] (query head ``i`` reads key/value head ``i
    // (h / hk)``); log_g: [b, s, hk] (``<= 0``); mask: [b, s] (True = a real
    token). Everything float32 inside, ``v``'s dtype out. ``chunk`` must tile
    ``s``."""
    b, s, h, d = q.shape
    hk = k.shape[2]
    rep = h // hk
    if s % chunk:
        raise ValueError(f"chunks of {chunk} positions do not tile s={s}")
    if mask is None:
        mask = jnp.ones((b, s), bool)
    mask = mask.astype(bool)
    n = s // chunk
    f32 = lambda x: x.astype(jnp.float32)
    g = _chunk_gates(log_g, mask, chunk)
    # chunk-major: [n, b, C, ...]
    split = lambda x: jnp.moveaxis(x.reshape(b, n, chunk, *x.shape[2:]), 1, 0)
    qc = split(f32(q).reshape(b, s, hk, rep, d) / math.sqrt(d))
    kc, vc, gc, mc = split(f32(k)), split(f32(v)), split(g), split(mask)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    dim = phi(jnp.zeros((d,), jnp.float32)).shape[-1]

    def step(carry, xs):
        S, z = carry  # [b, hk, D, d], [b, hk, D]
        qi, ki, vi, gi, mi = xs  # [b, C, hk, rep, d], [b, C, hk, d] x 2, [b, C, hk], [b, C]
        ok = causal[None, :, :, None] & mi[:, None, :, None]  # [b, t, j, hk]
        decay = jnp.exp(jnp.where(ok, gi[:, :, None, :] - gi[:, None, :, :], -jnp.inf))
        scores = jnp.einsum("bthrd,bjhd->bthrj", qi, ki)
        w = _weights(scores) * jnp.moveaxis(decay, 2, 3)[:, :, :, None, :]  # [b, t, hk, rep, j]
        num = jnp.einsum("bthrj,bjhd->bthrd", w, vi)
        den = jnp.sum(w, axis=-1)
        into = jnp.exp(gi)[..., None]  # [b, C, hk, 1]: the state decays to each query
        pq = phi(qi)  # [b, C, hk, rep, D]
        num = num + into[..., None] * jnp.einsum("bthrD,bhDd->bthrd", pq, S)
        den = den + into * jnp.einsum("bthrD,bhD->bthr", pq, z)
        o = _ratio(num, den[..., None], EPS)
        last = gi[:, -1]  # [b, hk]: the whole chunk's decay
        wk = jnp.where(mi[..., None], jnp.exp(last[:, None, :] - gi), 0.0)  # [b, C, hk]
        pk = phi(ki) * wk[..., None]  # [b, C, hk, D]
        S = jnp.exp(last)[..., None, None] * S + jnp.einsum("bjhD,bjhd->bhDd", pk, vi)
        z = jnp.exp(last)[..., None] * z + jnp.sum(pk, axis=1)
        return (S, z), o

    init = (jnp.zeros((b, hk, dim, d), jnp.float32), jnp.zeros((b, hk, dim), jnp.float32))
    _, o = lax.scan(step, init, (qc, kc, vc, gc, mc))  # [n, b, C, hk, rep, d]
    o = jnp.moveaxis(o, 0, 1).reshape(b, s, h, d)
    return o.astype(v.dtype)


@functools.partial(jax.custom_jvp, nondiff_argnums=(5, 6))
def _retention(q, k, v, log_g, mask, chunk, interpret):
    if interpret is None:
        return power_retention_plain(q, k, v, log_g, mask, chunk=chunk)
    from deepdfa_tpu.ops.power_retention_kernel import retention_forward

    return retention_forward(q, k, v, log_g, mask, chunk=chunk, interpret=interpret)


@_retention.defjvp
def _retention_jvp(chunk, interpret, primals, tangents):
    raise NotImplementedError(
        "power_retention has no backward: it serves a frozen decoder, and a trained "
        "linear-attention layer needs one (ops/power_retention_kernel.py)")


def power_retention(q, k, v, log_g, mask=None, *, chunk: int,
                    interpret: bool | None = None) -> jnp.ndarray:
    """``o`` of the module docstring in ``v``'s dtype. q: [b, s, h * d]; k,
    v: [b, s, hk * d] (heads side by side, as the projections give them);
    log_g: [b, s, hk]; mask: [b, s]; ``chunk`` positions that tile ``s``.
    Returns [b, s, h * d].

    ``interpret=None`` is the plain form; ``False`` / ``True`` the kernel,
    compiled / under the Pallas interpreter, for a shape :func:`supports`
    takes. Raises where it is differentiated."""
    b, s, hd = v.shape
    hk = log_g.shape[-1]
    d = hd // hk
    h = q.shape[-1] // d
    mask = jnp.ones((b, s), bool) if mask is None else mask.astype(bool)
    if interpret is None:
        split = lambda x, heads: x.reshape(b, s, heads, d)
        o = _retention(split(q, h), split(k, hk), split(v, hk), log_g, mask, chunk, None)
        return o.reshape(b, s, h * d)
    if not supports(s, h, hk, d, chunk):
        raise ValueError(f"the power-retention kernel takes no [s={s}, heads={h}, "
                         f"kv_heads={hk}, head_dim={d}] in chunks of {chunk}")
    return _retention(q, k, v, log_g, mask, chunk, interpret)
