"""Causal latent attention without its scores in HBM — a Pallas TPU kernel,
forward only.

Latent attention's keys are two parts: a ``nope`` part a head (128 wide) and
one ``rope`` part (64 wide) that all heads share; its values are 128 wide.
Written with XLA ops (``ops/ring_attention.blocked_causal_attention``) the
shared part is broadcast to every head and concatenated (201 MB at
``[4, 2048, 64, 192]``), and each query block's ``[b, h, block_q, keys]``
float32 scores are written, masked twice, soft-maxed, tested for empty rows,
cast and read again. Here a ``[block_q, block_k]`` tile of scores lives in
VMEM only, and the softmax runs over the key tiles as they come (running
maximum and sum in float32, the accumulator normalised once at the end).

**Tiles with nothing in them are never visited.** A query tile loops over the
key tiles from the first that holds a real key (``first_tile``, from the pad
mask, a scalar a row in SMEM) to its own diagonal: above the diagonal nothing
is computed, and under left padding neither are the leading pad keys nor —
their loop being empty — the query tiles that are wholly pad, which return
zeros as ``full_attention``'s ``row_valid`` makes them. Any mask is computed
exactly (a pad key inside a visited tile is masked in the tile); only left
padding is also *skipped*.

Same arithmetic as the XLA path in bfloat16: operands as they arrive, products
accumulated in float32, the scale, the masks and the softmax in float32, the
probabilities cast to the values' dtype for the product with ``v``. The
192-wide contraction is a 128-wide product with the head's own keys plus a
64-wide one with the shared keys, both into float32.

Layout: everything stays as the projections give it, heads side by side along
the lanes — ``kv`` is ``[b, s, h * 256]`` with a head's keys and values in
neighbouring 128-lane blocks, and ``o`` leaves as ``[b, s, h * 128]``, what
``o_proj`` takes. A grid step serves the two heads whose rope parts share one
128-lane block of ``q_rope``; each is picked out by zeroing the other's lanes
against the shared keys laid twice side by side (the product contracts over
128 lanes either way). The whole row of a head pair's keys and values (2 MB at
2048 keys) is resident while the query tiles pass, so it is read once.

Not differentiated by a kernel: the decoder this serves is frozen. The
``custom_vjp``'s backward is the gradient of ``blocked_causal_attention``,
recomputed.

``interpret=True`` runs the same kernel under the Pallas interpreter (CPU
tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepdfa_tpu.ops.ring_attention import blocked_causal_attention

__all__ = ["latent_attention", "supports", "first_tile", "visited_tiles"]

LANES = 128
MAX_KEYS = 4096  # the resident row of a head pair's keys and values: 4 MiB
_NEG_INF = -1e30  # ring_attention's: keeps exp() and where() NaN-free
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)


def supports(seq_len: int, num_heads: int, nope_dim: int, rope_dim: int, v_dim: int) -> bool:
    """Whether the kernel takes this shape: keys and values one 128-lane block
    a head, two heads' rope parts to a block, whole 128-row tiles."""
    return (nope_dim == LANES and v_dim == LANES and 2 * rope_dim == LANES
            and num_heads % 2 == 0 and seq_len % LANES == 0 and seq_len <= MAX_KEYS)


def _block(seq_len: int) -> int:
    return next(b for b in (512, 256, 128) if seq_len % b == 0)


def first_tile(kv_mask: jnp.ndarray, block_k: int) -> jnp.ndarray:
    """``[b]`` int32: a row's first key tile that holds a real key (the
    number of tiles where none does)."""
    b, s = kv_mask.shape
    real = kv_mask.reshape(b, s // block_k, block_k).any(axis=-1)
    return jnp.where(real.any(axis=-1), jnp.argmax(real, axis=-1), s // block_k).astype(jnp.int32)


def visited_tiles(qi, block_q: int, block_k: int):
    """``(diag, hi)``: query tile ``qi`` of a row visits the key tiles
    ``[first_tile, hi)``; those from ``diag`` on reach the diagonal and are
    masked by column, the ones before lie wholly under it."""
    q_start = qi * block_q
    return q_start // block_k, (q_start + block_q + block_k - 1) // block_k


def _kernel(first_ref, mask_ref, qn_ref, qr_ref, kv_ref, kr_ref, o_ref,
            m_sc, l_sc, acc_sc, *, block_k: int, scale: float):
    bi, qi = pl.program_id(0), pl.program_id(2)
    block_q = qn_ref.shape[1]
    lo = first_ref[bi]
    diag, hi = visited_tiles(qi, block_q, block_k)
    rope_lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1) // (LANES // 2)
    row = qi * block_q + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    col = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    for j in range(2):  # the two heads of this block
        qn = qn_ref[0, :, j * LANES:(j + 1) * LANES]
        # selected in float32: the v5e's VPU has no bfloat16
        qr = jnp.where(rope_lane == j, qr_ref[0].astype(jnp.float32), 0.0).astype(qn.dtype)
        m_sc[...] = jnp.full(m_sc.shape, _NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

        def tile(ki, _, *, causal: bool):
            start = pl.multiple_of(ki * block_k, block_k)
            keys = pl.ds(start, block_k)
            kn = kv_ref[0, keys, 2 * j * LANES:(2 * j + 1) * LANES]
            v = kv_ref[0, keys, (2 * j + 1) * LANES:(2 * j + 2) * LANES]
            s = (lax.dot_general(qn, kn, _NT, preferred_element_type=jnp.float32)
                 + lax.dot_general(qr, kr_ref[0, keys, :], _NT,
                                   preferred_element_type=jnp.float32)) * scale
            keep = mask_ref[0, ki] != 0  # [1, block_k]
            if causal:
                keep = keep & (start + col <= row)
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

        lax.fori_loop(lo, diag, functools.partial(tile, causal=False), None)
        lax.fori_loop(jnp.maximum(lo, diag), hi, functools.partial(tile, causal=True), None)
        # a query that saw no key (every left pad) returns zeros
        seen = m_sc[...] > _NEG_INF / 2
        o = jnp.where(seen, acc_sc[...] / jnp.where(seen, l_sc[...], 1.0), 0.0)
        o_ref[0, :, j * LANES:(j + 1) * LANES] = o.astype(o_ref.dtype)


# jitted: a decoder's attention blocks share one traced and lowered copy
@functools.partial(jax.jit, static_argnames=("num_heads", "block_q", "block_k", "interpret"))
def _forward(q_nope, q_rope, k_rope, kv, kv_mask, num_heads, block_q, block_k, interpret):
    b, s, _ = q_nope.shape
    dn, dr = q_nope.shape[-1] // num_heads, k_rope.shape[-1]
    dv = kv.shape[-1] // num_heads - dn
    mask = kv_mask.astype(jnp.int32).reshape(b, s // block_k, 1, block_k)
    row = lambda w: pl.BlockSpec((1, s, w), lambda bi, hb, qi, first: (bi, 0, hb))
    tile = lambda w: pl.BlockSpec((1, block_q, w), lambda bi, hb, qi, first: (bi, qi, hb))
    return pl.pallas_call(
        functools.partial(_kernel, block_k=block_k, scale=(dn + dr) ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            # the query tiles innermost: a head pair's row of keys stays put
            grid=(b, num_heads // 2, s // block_q),
            in_specs=[
                pl.BlockSpec((1, s // block_k, 1, block_k),
                             lambda bi, hb, qi, first: (bi, 0, 0, 0)),
                tile(2 * dn), tile(2 * dr), row(2 * (dn + dv)),
                pl.BlockSpec((1, s, 2 * dr), lambda bi, hb, qi, first: (bi, 0, 0)),
            ],
            out_specs=tile(2 * dv),
            scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                            pltpu.VMEM((block_q, 1), jnp.float32),
                            pltpu.VMEM((block_q, dv), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, s, num_heads * dv), q_nope.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="latent_attention_fwd",
    )(first_tile(kv_mask, block_k), mask, q_nope, q_rope, kv,
      jnp.concatenate([k_rope, k_rope], axis=-1))


def _blocked(q_nope, q_rope, k_rope, kv, kv_mask, num_heads):
    """The same attention through ``blocked_causal_attention``: heads apart,
    the shared keys broadcast to each and concatenated."""
    b, s, _ = q_nope.shape
    heads = lambda x: x.reshape(b, s, num_heads, -1)
    q_nope, q_rope, kv = heads(q_nope), heads(q_rope), heads(kv)
    dn = q_nope.shape[-1]
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope[:, :, None, :], q_rope.shape)], axis=-1)
    out = blocked_causal_attention(
        jnp.concatenate([q_nope, q_rope], axis=-1), k, kv[..., dn:], kv_mask=kv_mask)
    return out.reshape(b, s, -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _attention(q_nope, q_rope, k_rope, kv, kv_mask, num_heads, block_q, block_k, interpret):
    return _forward(q_nope, q_rope, k_rope, kv, kv_mask, num_heads, block_q, block_k, interpret)


def _attention_fwd(q_nope, q_rope, k_rope, kv, kv_mask, num_heads, block_q, block_k, interpret):
    out = _forward(q_nope, q_rope, k_rope, kv, kv_mask, num_heads, block_q, block_k, interpret)
    return out, (q_nope, q_rope, k_rope, kv, kv_mask)


def _attention_bwd(num_heads, block_q, block_k, interpret, residuals, do):
    *operands, kv_mask = residuals
    _, vjp = jax.vjp(lambda *xs: _blocked(*xs, kv_mask, num_heads), *operands)
    return (*vjp(do), None)


_attention.defvjp(_attention_fwd, _attention_bwd)


def latent_attention(
    q_nope: jnp.ndarray,
    q_rope: jnp.ndarray,
    k_rope: jnp.ndarray,
    kv: jnp.ndarray,
    kv_mask: jnp.ndarray | None = None,
    *,
    num_heads: int,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Causal (by column) softmax attention of latent-attention operands,
    scale ``(nope + rope) ** -0.5``; a query with no key to attend returns
    zeros. Differentiable (the backward is ``blocked_causal_attention``'s).

    Heads side by side, as the projections give them: q_nope
    ``[b, s, h * nope]``; q_rope ``[b, s, h * rope]``, rotated; k_rope
    ``[b, s, rope]``, rotated, shared by the heads; kv
    ``[b, s, h * (nope + v)]``, a head's keys then its values; kv_mask
    ``[b, s]`` (True = a real key). Returns ``[b, s, h * v]``. The shape must
    pass :func:`supports`; the tiles default to 512 where ``s`` allows."""
    b, s, _ = q_nope.shape
    dn, dr = q_nope.shape[-1] // num_heads, k_rope.shape[-1]
    dv = kv.shape[-1] // num_heads - dn
    if not supports(s, num_heads, dn, dr, dv):
        raise ValueError(f"latent_attention takes no [s={s}, heads={num_heads}, nope={dn}, "
                         f"rope={dr}, v={dv}]")
    block_q, block_k = block_q or _block(s), block_k or _block(s)
    if s % block_q or s % block_k or block_q % 8 or block_k % LANES:
        raise ValueError(f"tiles {block_q} x {block_k} do not tile s={s}")
    if kv_mask is None:
        kv_mask = jnp.ones((b, s), bool)
    return _attention(q_nope, q_rope, k_rope, kv, kv_mask, num_heads, block_q, block_k,
                      interpret)
