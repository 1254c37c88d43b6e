"""Routed-expert feed-forward over the experts a chip holds.

The expert layer of a sparse decoder is told a *range* of experts
(``[lo, lo + n)`` of the router's outputs) and computes, for its own tokens,
the part of the layer's result that those experts give — the statement is the
same on one chip and on a mesh whose expert axis gives each chip its range.
Nothing here computes an absent expert or stands in for one.

Mechanism — ``ops/segment.py``'s problem on the MXU:

1. every (token, choice) assignment to a held expert gets a sort key (its
   local expert id; everything else sorts last), one stable ``argsort`` groups
   them by expert;
2. the sorted assignments are consumed ``rows`` at a time by a ``while_loop``
   whose trip count is ``ceil(held / rows)`` — **no capacity limit and no
   dropped token**: under any imbalance (every token to one expert included)
   the loop simply runs longer, and the memory it needs stays ``rows`` wide;
3. each chunk is one grouped matrix product per projection
   (:func:`grouped_matmul`: jax's stock megablox kernel on the TPU, which
   visits only the row tiles that hold assignments; ``lax.ragged_dot``
   elsewhere), gated SiLU in float32, and a scatter-add of the gate-weighted
   rows back onto their tokens.

The count of rows handed to the grouped products (the sum of the group sizes
each call was given: rows outside a group are not computed) is returned
beside the result, so ``held - computed`` (the assignments dropped) is a
reading, not a claim.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["grouped_matmul", "held_expert_ffn"]

# megablox tiles (m, k, n) for one v5e core: [512, 1024] and [1024, 1024]
# bf16 operand tiles double-buffered plus a [512, 1024] f32 accumulator stay
# well inside the 16 MiB of scoped VMEM
_GMM_TILING = (512, 1024, 1024)


def grouped_matmul(x: jnp.ndarray, w: jnp.ndarray, group_sizes: jnp.ndarray) -> jnp.ndarray:
    """``x[rows of group g] @ w[g]`` for every group, float32 out.

    x: [m, k] with rows sorted by group; w: [g, k, n]; group_sizes: [g] int32
    whose sum may be less than ``m`` — rows past it hold no assignment and
    their output is unspecified (the caller masks them)."""
    m, k = x.shape
    n = w.shape[-1]
    tm, tk, tn = _GMM_TILING
    if jax.default_backend() == "tpu" and m % tm == 0 and k % 128 == 0 and n % 128 == 0:
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

        return gmm(x, w, group_sizes, preferred_element_type=jnp.float32,
                   tiling=(tm, min(tk, k), min(tn, n)))
    return lax.ragged_dot(x, w, group_sizes, preferred_element_type=jnp.float32)


def held_expert_ffn(
    u: jnp.ndarray,
    choice: jnp.ndarray,
    gates: jnp.ndarray,
    w_gate: jnp.ndarray,
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,
    *,
    lo: int,
    rows: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``sum over (choice in held) of gate * W_down(silu(W_gate u) * (W_up u))``.

    u: [t, d] tokens; choice: [t, k] global expert ids (anything outside
    ``[lo, lo + n)`` is not this chip's: absent, zero-compute, or -1 for a pad
    token); gates: [t, k] float32; w_gate / w_up: [n, d, f]; w_down:
    [n, f, d]; ``rows``: assignments consumed per loop iteration.

    Returns ``(out [t, d] float32, computed)``: ``computed`` sums the group
    sizes the grouped products were given, trip by trip — the rows they
    produced, counted apart from the positions the loop visited."""
    t, d = u.shape
    k = choice.shape[1]
    n = w_gate.shape[0]
    a = t * k
    local = choice.reshape(a) - lo
    held = (local >= 0) & (local < n)
    key = jnp.where(held, local, n).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)  # held assignments first, by expert
    # a compare-and-sum, not a bincount: that lowers to a scatter, slow on the TPU
    sizes = jnp.sum(key[:, None] == jnp.arange(n, dtype=jnp.int32)[None, :], axis=0,
                    dtype=jnp.int32)
    ends = jnp.cumsum(sizes)
    n_held = ends[-1]
    token = (order // k).astype(jnp.int32)
    gate = gates.reshape(a)[order]
    offs = jnp.arange(rows, dtype=jnp.int32)

    def chunk(carry):
        start, out, computed = carry
        pos = start + offs
        valid = pos < n_held
        pos = jnp.minimum(pos, a - 1)
        tok = token[pos]
        x = u[tok]
        # the part of each expert's run of rows that falls inside this chunk
        sz = jnp.clip(ends - start, 0, rows) - jnp.clip(ends - sizes - start, 0, rows)
        h = jax.nn.silu(grouped_matmul(x, w_gate, sz)) * grouped_matmul(x, w_up, sz)
        y = grouped_matmul(h.astype(u.dtype), w_down, sz)
        y = jnp.where(valid[:, None], y * gate[pos][:, None], 0.0)
        out = out.at[jnp.where(valid, tok, t)].add(y, mode="drop")
        return start + rows, out, computed + jnp.sum(sz, dtype=jnp.int32)

    _, out, computed = lax.while_loop(
        lambda c: c[0] < n_held, chunk,
        (jnp.zeros((), jnp.int32), jnp.zeros((t, d), jnp.float32), jnp.zeros((), jnp.int32)))
    return out, computed
