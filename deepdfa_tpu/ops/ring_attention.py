"""Ring attention: exact attention over sequence-sharded inputs.

Long-context / sequence-parallelism kernel for the LLM layer. The reference
has **no** long-context story — it truncates every function to
``block_size <= 2048`` tokens (``MSIVD/msivd/train.py:199-207``); SURVEY.md §5
assigns the TPU framework a real sequence-sharding design instead. This
module is that design:

- the sequence axis is sharded over the mesh's ``sp`` axis;
- each device holds one contiguous block of Q and one of K/V;
- K/V blocks rotate around the ring via ``lax.ppermute`` (ICI
  neighbour-to-neighbour, bandwidth-optimal — no all-gather of the full
  sequence ever materialises);
- partial attention outputs are combined with the online-softmax
  (flash-attention) recurrence, in float32, so the result is *exact* full
  attention, not an approximation.

Communication overlaps compute naturally: XLA schedules the ``ppermute`` of
step ``i+1``'s K/V against step ``i``'s matmuls.

Also exports :func:`full_attention`, the single-device reference used for the
parity-mode (truncated, block_size ≤ 2048) path and for tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = [
    "full_attention",
    "blocked_causal_attention",
    "blocked_key_ranges",
    "ring_attention",
    "ring_attention_sharded",
]

_NEG_INF = -1e30  # large-negative instead of -inf: keeps exp()/where() NaN-free


def _repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """GQA: repeat KV heads to match query heads. [b, s, h_kv, d] -> [b, s, h, d]."""
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def full_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    kv_mask: jnp.ndarray | None = None,
    q_positions: jnp.ndarray | None = None,
    kv_positions: jnp.ndarray | None = None,
    window: int | None = None,
) -> jnp.ndarray:
    """Plain softmax attention, fp32 accumulation.

    q: [b, sq, h, d]; k/v: [b, sk, h_kv, d]; kv_mask: [b, sk] (True = attend).
    Positions default to ``arange`` and only matter for causal masking.
    ``window`` (causal only): key ``j`` is visible to query ``t`` iff
    ``t - window < j <= t`` — the query itself and the ``window - 1`` before it.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    k = _repeat_kv(k, h // k.shape[2])
    v = _repeat_kv(v, h // v.shape[2])
    scale = d**-0.5
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        qpos = jnp.arange(sq) if q_positions is None else q_positions
        kpos = jnp.arange(sk) if kv_positions is None else kv_positions
        causal_mask = kpos[None, :] <= qpos[:, None]  # [sq, sk]
        if window is not None:
            causal_mask &= kpos[None, :] > qpos[:, None] - window
        scores = jnp.where(causal_mask[None, None], scores, _NEG_INF)
    if kv_mask is not None:
        scores = jnp.where(kv_mask[:, None, None, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    if kv_mask is not None:
        # Fully-masked query rows (all-padding examples) would softmax to
        # uniform over _NEG_INF scores; return zeros for them instead.
        row_valid = jnp.any(scores > _NEG_INF / 2, axis=-1)  # [b, h, q]
        probs = jnp.where(row_valid[..., None], probs, 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out.astype(q.dtype)


def blocked_causal_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    kv_mask: jnp.ndarray | None = None,
    block_q: int = 256,
    window: int | None = None,
) -> jnp.ndarray:
    """Causal self-attention in blocks over the queries: block ``i`` attends
    to keys ``[0, end of block i)`` only, so the scores are never whole
    (``[b, h, block_q, keys]`` at a time) and the blocks above the diagonal
    are never computed; with a ``window`` (:func:`full_attention`'s) a block
    reads from its first query's oldest visible key on, never the whole
    prefix (:func:`blocked_key_ranges`). The query/key width may differ from
    the value width (latent attention: 192 against 128); the scale is the
    query width's.

    q: [b, s, h, dk]; k: [b, s, h_kv, dk]; v: [b, s, h_kv, dv]; kv_mask:
    [b, s] (True = attend). Returns [b, s, h, dv]."""
    outs = []
    for start, end, lo in blocked_key_ranges(q.shape[1], block_q, window):
        outs.append(full_attention(
            q[:, start:end], k[:, lo:end], v[:, lo:end], causal=True,
            kv_mask=None if kv_mask is None else kv_mask[:, lo:end],
            q_positions=jnp.arange(start, end),
            kv_positions=jnp.arange(lo, end) if lo else None, window=window))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


def blocked_key_ranges(s: int, block_q: int, window: int | None = None):
    """``(start, end, lo)`` of every block of :func:`blocked_causal_attention`
    over ``s`` positions: queries ``[start, end)`` read keys ``[lo, end)``, so
    ``sum((end - start) * (end - lo))`` is the query-key pairs it multiplies."""
    for start in range(0, s, block_q):
        yield (start, min(start + block_q, s),
               0 if window is None else max(0, start - window + 1))


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str = "sp",
    causal: bool = True,
    kv_mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Per-shard ring attention body. Call inside ``shard_map``/``pmap`` where
    the sequence axis is sharded over ``axis_name``.

    q: [b, s_loc, h, d]; k/v: [b, s_loc, h_kv, d]; kv_mask: [b, s_loc]
    (local blocks; global seq = n_shards * s_loc, shard i holding positions
    ``[i*s_loc, (i+1)*s_loc)``).
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    n_rep = h // k.shape[2]
    scale = d**-0.5

    qf = q.astype(jnp.float32)
    local = jnp.arange(s_loc)
    q_pos = idx * s_loc + local  # [s_loc] global positions of local queries

    def step(j, carry):
        k_blk, v_blk, m_blk, acc, m, l = carry
        src = (idx - j) % n  # which shard this K/V block originated on
        scores = (
            jnp.einsum(
                "bqhd,bkhd->bhqk",
                qf,
                _repeat_kv(k_blk, n_rep).astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
            * scale
        )
        k_pos = src * s_loc + local
        mask = jnp.ones((s_loc, s_loc), dtype=bool)
        if causal:
            mask = k_pos[None, :] <= q_pos[:, None]
        allowed = mask[None, None] & m_blk[:, None, None, :]  # [b, 1|h, q, k]
        scores = jnp.where(allowed, scores, _NEG_INF)

        # online-softmax merge (flash recurrence), fp32. ``p`` is zeroed on
        # disallowed keys explicitly: with a finite _NEG_INF, a fully-masked
        # row has m_new == _NEG_INF and exp(scores - m_new) == 1, which would
        # otherwise count masked keys into l and defeat the l>0 guard below.
        m_new = jnp.maximum(m, scores.max(axis=-1))  # [b, h, q]
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None]) * allowed  # [b, h, q, k]
        l_new = l * alpha + p.sum(axis=-1)
        pv = jnp.einsum(
            "bhqk,bkhd->bqhd",
            p,
            _repeat_kv(v_blk, n_rep).astype(jnp.float32),
        )
        acc_new = acc * alpha.transpose(0, 2, 1)[..., None] + pv

        perm = [(i, (i + 1) % n) for i in range(n)]
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        m_nxt = lax.ppermute(m_blk, axis_name, perm)
        return k_nxt, v_nxt, m_nxt, acc_new, m_new, l_new

    m0 = jnp.full((b, h, s_loc), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc), jnp.float32)
    acc0 = jnp.zeros((b, s_loc, h, d), jnp.float32)
    mask0 = (
        jnp.ones((b, s_loc), dtype=bool) if kv_mask is None else kv_mask.astype(bool)
    )
    # Match the manual-axes "varying" type of the loop outputs: constants start
    # unvarying under shard_map, while ppermute/collective outputs vary.
    def _vma_of(x):
        return jax.typeof(x).vma

    target_vma = frozenset().union(*(_vma_of(x) for x in (q, k, v)))

    def _vary(x):
        missing = tuple(target_vma - _vma_of(x))
        return lax.pcast(x, missing, to="varying") if missing else x

    carry0 = tuple(_vary(x) for x in (k, v, mask0, acc0, m0, l0))
    _, _, _, acc, _, l = lax.fori_loop(0, n, step, carry0)
    l_t = l.transpose(0, 2, 1)[..., None]  # [b, q, h, 1]
    out = jnp.where(l_t > 0, acc / jnp.maximum(l_t, 1e-30), 0.0)
    return out.astype(q.dtype)


def ring_attention_sharded(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: jax.sharding.Mesh,
    *,
    causal: bool = True,
    kv_mask: jnp.ndarray | None = None,
    batch_axis: str = "dp",
    seq_axis: str = "sp",
) -> jnp.ndarray:
    """Global-array entry point: shard the sequence over ``seq_axis`` (and
    batch over ``batch_axis``) and run :func:`ring_attention` under
    ``shard_map``. Composes inside an outer ``jit``.
    """
    qkv_spec = P(batch_axis, seq_axis, None, None)
    mask_spec = P(batch_axis, seq_axis)
    body = functools.partial(ring_attention, axis_name=seq_axis, causal=causal)
    if kv_mask is None:
        fn = jax.shard_map(
            lambda q, k, v: body(q, k, v),
            mesh=mesh,
            in_specs=(qkv_spec, qkv_spec, qkv_spec),
            out_specs=qkv_spec,
        )
        return fn(q, k, v)
    fn = jax.shard_map(
        lambda q, k, v, m: body(q, k, v, kv_mask=m),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
        out_specs=qkv_spec,
    )
    return fn(q, k, v, kv_mask)
