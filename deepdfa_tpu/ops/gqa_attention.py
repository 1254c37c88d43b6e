"""Causal grouped-query attention, global or inside a window, without its
scores in HBM — a Pallas TPU kernel, forward only.

Written with XLA ops (``ops/ring_attention.blocked_causal_attention``) a query
block's ``[b, heads, block_q, keys]`` float32 scores are written, masked,
soft-maxed, tested for empty rows, cast and read again, and the key/value
heads are repeated to the query heads' count: at ``[2, 8192, 28 | 4, 128]``
that is 0.94 GB of scores a block of 512 queries and most of a step (PERF.md
section 6, PR 39). Here a ``[block_q, block_k]`` tile of scores lives in VMEM
only, the softmax runs over the key tiles as they come (running maximum and
sum in float32, the accumulator normalised once at the end), and one grid
step serves the query heads that share a key/value head, whose row of keys
and values (4 MiB at 8,192 keys) is resident while the query tiles pass: read
once, never repeated.

**Tiles with nothing in them are never visited.** A query tile loops over the
key tiles from the later of the first that holds a real key (``first_tile``,
from the pad mask, a scalar a row in SMEM) and the first its ``window``
reaches back to, up to its own diagonal: nothing above the diagonal, nothing
behind the band, and under left padding neither the leading pad keys nor —
their loop being empty, with query and key tiles of one size — the query
tiles that are wholly pad, which return zeros as ``full_attention``'s
``row_valid`` makes them. Tiles that lie wholly
under the diagonal and wholly inside the band take no positional mask; any
pad mask is computed exactly (a pad key inside a visited tile is masked in
the tile); only left padding is also *skipped*. :func:`visited_pairs` is the
count of query-key pairs the loops multiply, from the same bounds.

Same arithmetic as the XLA path in bfloat16: operands as they arrive, products
accumulated in float32, the scale, the masks and the softmax in float32, the
probabilities cast to the values' dtype for the product with ``v``.

Layout: everything stays as the projections give it, heads side by side along
the lanes — ``q`` is ``[b, s, heads * 128]``, ``k`` and ``v``
``[b, s, kv_heads * 128]``, and ``o`` leaves as ``o_proj`` takes it.

Not differentiated by a kernel: the decoder this serves is frozen. The
``custom_vjp``'s backward is the gradient of ``blocked_causal_attention``,
recomputed. ``interpret=True`` runs the same kernel under the Pallas
interpreter (CPU tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepdfa_tpu.ops.latent_attention import first_tile
from deepdfa_tpu.ops.ring_attention import blocked_causal_attention

__all__ = ["gqa_attention", "supports", "default_tile", "visited_tiles", "visited_pairs"]

LANES = 128
MAX_KEYS = 16384  # the resident row of one key/value head's keys and values: 8 MiB
_NEG_INF = -1e30  # ring_attention's: keeps exp() and where() NaN-free
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)


def supports(seq_len: int, num_heads: int, num_kv_heads: int, head_dim: int) -> bool:
    """Whether the kernel takes this shape: heads one 128-lane block each,
    whole 128-row tiles, a key/value head's row resident."""
    return (head_dim == LANES and num_heads % num_kv_heads == 0
            and seq_len % LANES == 0 and seq_len <= MAX_KEYS)


def default_tile(seq_len: int) -> int:
    """The side of the query and key tiles where none is asked for."""
    return next(b for b in (512, 256, 128) if seq_len % b == 0)


def visited_tiles(qi, block_q: int, block_k: int, window: int | None):
    """``(band, inner, diag, hi)``: query tile ``qi`` of a row visits the key
    tiles ``[max(first_tile, band), hi)``. Those in ``[inner, diag)`` lie
    wholly under the diagonal and wholly inside every query's window and take
    no positional mask; the ones before ``inner`` straddle the band's far
    edge, the ones from ``diag`` on the diagonal."""
    q_start = qi * block_q
    diag, hi = q_start // block_k, (q_start + block_q + block_k - 1) // block_k
    if window is None:
        return 0, 0, diag, hi
    # the first key the tile's first query sees; the first tile all of whose keys its last sees
    band = jnp.maximum(q_start - window + 1, 0) // block_k
    inner = (jnp.maximum(q_start + block_q - window, 0) + block_k - 1) // block_k
    return band, jnp.minimum(inner, diag), diag, hi


def visited_pairs(kv_mask: jnp.ndarray, block_q: int, block_k: int,
                  window: int | None) -> jnp.ndarray:
    """Query-key pairs one head of the kernel multiplies over the rows of
    ``kv_mask`` [b, s]: every visited tile whole. float32."""
    s = kv_mask.shape[1]
    first = first_tile(kv_mask, block_k)  # [b]
    tiles = jnp.zeros((), jnp.int32)
    for qi in range(s // block_q):
        band, _, _, hi = visited_tiles(qi, block_q, block_k, window)
        tiles = tiles + jnp.sum(jnp.maximum(hi - jnp.maximum(first, band), 0))
    return tiles.astype(jnp.float32) * (block_q * block_k)


def _kernel(first_ref, mask_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
            block_k: int, window: int | None, scale: float):
    bi, qi = pl.program_id(0), pl.program_id(2)
    block_q = q_ref.shape[1]
    band, inner, diag, hi = visited_tiles(qi, block_q, block_k, window)
    lo = jnp.maximum(first_ref[bi], band)
    row = qi * block_q + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    col = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    for j in range(q_ref.shape[2] // LANES):  # the query heads of this key/value head
        q = q_ref[0, :, j * LANES:(j + 1) * LANES]
        m_sc[...] = jnp.full(m_sc.shape, _NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

        def tile(ki, _, *, edge: bool):
            start = pl.multiple_of(ki * block_k, block_k)
            keys = pl.ds(start, block_k)
            v = v_ref[0, keys, :]
            s = lax.dot_general(q, k_ref[0, keys, :], _NT,
                                preferred_element_type=jnp.float32) * scale
            keep = mask_ref[0, ki] != 0  # [1, block_k]
            if edge:
                keep = keep & (start + col <= row)
                if window is not None:
                    keep = keep & (start + col > row - window)
            s = jnp.where(keep, s, _NEG_INF)
            m_prev = m_sc[...]
            m = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            # a row with no key yet keeps m at _NEG_INF and gathers exp(0)s:
            # its first real key's alpha is 0.0 and wipes them
            alpha = jnp.exp(m_prev - m)
            p = jnp.exp(s - m)
            l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
            acc_sc[...] = alpha * acc_sc[...] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_sc[...] = m

        if window is not None:  # the band's far edge
            lax.fori_loop(lo, jnp.maximum(lo, inner), functools.partial(tile, edge=True), None)
        lax.fori_loop(jnp.maximum(lo, inner), diag, functools.partial(tile, edge=False), None)
        lax.fori_loop(jnp.maximum(lo, diag), hi, functools.partial(tile, edge=True), None)
        # a query that saw no key (every left pad) returns zeros
        seen = m_sc[...] > _NEG_INF / 2
        o = jnp.where(seen, acc_sc[...] / jnp.where(seen, l_sc[...], 1.0), 0.0)
        o_ref[0, :, j * LANES:(j + 1) * LANES] = o.astype(o_ref.dtype)


# jitted: a decoder's attention layers of one kind share one traced and lowered copy
@functools.partial(jax.jit, static_argnames=("num_kv_heads", "window", "block_q", "block_k",
                                             "interpret"))
def _forward(q, k, v, kv_mask, num_kv_heads, window, block_q, block_k, interpret):
    b, s, width = q.shape
    group = width // num_kv_heads  # the lanes of one key/value head's query heads
    mask = kv_mask.astype(jnp.int32).reshape(b, s // block_k, 1, block_k)
    row = pl.BlockSpec((1, s, LANES), lambda bi, g, qi, first: (bi, 0, g))
    tile = pl.BlockSpec((1, block_q, group), lambda bi, g, qi, first: (bi, qi, g))
    return pl.pallas_call(
        functools.partial(_kernel, block_k=block_k, window=window, scale=LANES ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            # the query tiles innermost: a key/value head's row stays put
            grid=(b, num_kv_heads, s // block_q),
            in_specs=[
                pl.BlockSpec((1, s // block_k, 1, block_k),
                             lambda bi, g, qi, first: (bi, 0, 0, 0)),
                tile, row, row,
            ],
            out_specs=tile,
            scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                            pltpu.VMEM((block_q, 1), jnp.float32),
                            pltpu.VMEM((block_q, LANES), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="gqa_attention_fwd",
    )(first_tile(kv_mask, block_k), mask, q, k, v)


def _blocked(q, k, v, kv_mask, num_kv_heads, window):
    """The same attention through ``blocked_causal_attention``: heads apart."""
    b, s, _ = q.shape
    heads = lambda x: x.reshape(b, s, -1, LANES)
    return blocked_causal_attention(
        heads(q), heads(k), heads(v), kv_mask=kv_mask, window=window).reshape(q.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _attention(q, k, v, kv_mask, num_kv_heads, window, block_q, block_k, interpret):
    return _forward(q, k, v, kv_mask, num_kv_heads, window, block_q, block_k, interpret)


def _attention_fwd(q, k, v, kv_mask, num_kv_heads, window, block_q, block_k, interpret):
    out = _forward(q, k, v, kv_mask, num_kv_heads, window, block_q, block_k, interpret)
    return out, (q, k, v, kv_mask)


def _attention_bwd(num_kv_heads, window, block_q, block_k, interpret, residuals, do):
    *operands, kv_mask = residuals
    _, vjp = jax.vjp(lambda *xs: _blocked(*xs, kv_mask, num_kv_heads, window), *operands)
    return (*vjp(do), None)


_attention.defvjp(_attention_fwd, _attention_bwd)


def gqa_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    kv_mask: jnp.ndarray | None = None,
    *,
    num_kv_heads: int,
    window: int | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Causal softmax attention of grouped-query operands, scale
    ``128 ** -0.5``; with a ``window`` key ``j`` is visible to query ``t`` iff
    ``t - window < j <= t``; a query with no key to attend returns zeros.
    Differentiable (the backward is ``blocked_causal_attention``'s).

    Heads side by side, as the projections give them: q ``[b, s, h * 128]``
    (rotated where the layer rotates); k, v ``[b, s, kv_heads * 128]``, query
    head ``i`` reading key/value head ``i // (h / kv_heads)``; kv_mask
    ``[b, s]`` (True = a real key). Returns ``[b, s, h * 128]``. The shape
    must pass :func:`supports`; the tiles default to 512 where ``s`` allows."""
    b, s, width = q.shape
    if not supports(s, width // LANES, num_kv_heads, k.shape[-1] // num_kv_heads):
        raise ValueError(f"gqa_attention takes no [s={s}, q width={width}, "
                         f"kv width={k.shape[-1]} over {num_kv_heads} heads]")
    block_q, block_k = block_q or default_tile(s), block_k or default_tile(s)
    if s % block_q or s % block_k or block_q % 8 or block_k % LANES:
        raise ValueError(f"tiles {block_q} x {block_k} do not tile s={s}")
    if kv_mask is None:
        kv_mask = jnp.ones((b, s), bool)
    return _attention(q, k, v, kv_mask, num_kv_heads, window, block_q, block_k, interpret)
