"""Bidirectional self-attention without the score tensor in HBM — Pallas TPU
kernels, forward and backward.

``softmax(q k^T * scale + mask) v`` written with XLA ops materialises the
``[b, h, s, s]`` float32 scores, the probabilities autodiff keeps for the
backward, and ``dP`` / ``dS`` at the same size again: at CodeBERT's
``[16, 12, 512, 512]`` that is 201 MB a tensor, ~3 GB of HBM traffic a layer
for a tenth of the layer's FLOPs (PERF.md section 6, PR 30). Here a
``[block_q, s]`` tile of scores — a block of queries against the whole row of
keys — lives in VMEM only:

- **forward**: the tile's softmax in float32, ``o`` and one
  ``lse = max + log(sum)`` a row kept for the backward;
- **backward**: one kernel recomputes the tile's probabilities from ``q, k``
  and ``lse`` and makes ``dq``, ``dk``, ``dv`` from it in the same visit
  (five products a tile, nothing recomputed twice).

The key axis is not blocked. The chip chose that (one 512-key block ran 9.9
ms a step's twelve layers, two 256-key blocks 19.3 ms, PERF.md section 6),
the sequences this serves allow it (a RoBERTa encoder has 514 positions;
:func:`supports` stops at 2048 keys, a 1 MiB tile), and the backward needs it:
``dS = P * (dP - sum_k P dP)`` subtracts two nearly equal numbers wherever a
row's values resemble each other, so the row sum has to be taken over the
very ``P`` and ``dP`` the tile holds, as XLA's backward takes it. The usual
``rowsum(o * do)`` in its place differs by the bfloat16 rounding of ``P``
inside ``o`` (2^-9, one way for a whole near-uniform row) and read ``delta_gap``
0.10 against 0.004-0.006 in the benchmark's comparison.

Same arithmetic as the XLA path at default matmul precision: arrays stay in
their dtype (float32 for CodeBERT), every product rounds its operands to
bfloat16 for one MXU pass and accumulates in float32; the scale, the mask, the
softmax, its statistics and all accumulators are float32.

Layout: ``q, k, v`` arrive as the projections give them, ``[b, s, h * d]``
with the heads side by side along the lanes, and ``o`` leaves the same way —
no transposition to ``[b, h, s, d]`` on either side. A block is 128 lanes wide
(``128 // d`` heads; ``d`` lanes when ``d`` is a multiple of 128). A head
narrower than 128 lanes is picked out of its block by zeroing the other heads'
lanes of one operand: the product contracts over 128 lanes either way, so
this costs the MXU nothing and needs no lane shuffle; results are put back by
a select on the lane index. The ``lse`` of all heads share one ``[b, s, 128]``
array, lane = head.

The mask is per-token segment ids: a query attends the keys of its own
segment (pads among themselves, real tokens among themselves). Every row sees
at least itself, so no row of the softmax is empty.

``interpret=True`` runs the same kernels under the Pallas interpreter (CPU
tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "supports"]

LANES = 128
_MASKED = -1e9  # what the XLA path adds to a masked score: exp() gives 0.0
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b
MAX_KEYS = 2048  # a [128, 2048] float32 tile is 1 MiB, as [512, 512] is
# a few such tiles (scores, probabilities, their cotangents) and the
# double-buffered [s, 128] operand blocks: well under this, over the default
# scoped limit (16 MiB on the v5e) at the longest row. Both grids end in the
# one axis whose steps share an output block (the lse lanes, the dk/dv sums).
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)


def supports(seq_len: int, num_heads: int, head_dim: int) -> bool:
    """Whether the kernels take this shape: whole 128-row blocks of queries,
    a row of keys that fits a tile, heads that tile 128-lane blocks, one
    ``lse`` lane a head."""
    if seq_len % LANES or seq_len > MAX_KEYS or num_heads > LANES:
        return False
    if head_dim % LANES == 0:
        return True
    return LANES % head_dim == 0 and (num_heads * head_dim) % LANES == 0


def _block_q(seq_len: int) -> int:
    """The most queries whose tile stays within 1 MiB."""
    return next(b for b in (512, 256, 128) if seq_len % b == 0 and b * seq_len <= 512 * 512)


def _head_masks(width: int, head_dim: int):
    """One ``[1, width]`` lane mask a head of the block (``[None]`` when the
    block is one head)."""
    if width == head_dim:
        return [None]
    lane = lax.broadcasted_iota(jnp.int32, (1, width), 1)
    return [lane // head_dim == j for j in range(width // head_dim)]


def _only(x, mask):
    """``x`` with the other heads' lanes zeroed, as an MXU operand."""
    if mask is not None:  # selected in float32: the v5e's VPU has no bfloat16
        x = jnp.where(mask, x.astype(jnp.float32), 0.0)
    return x.astype(jnp.bfloat16)


def _by_head(parts, masks):
    """Each head's lanes from its own result."""
    out = parts[-1]
    for part, mask in zip(parts[-2::-1], masks[-2::-1]):
        out = jnp.where(mask, part, out)
    return out


def _stat_lane(ref_value, lane_index):
    """Column ``lane_index`` of a ``[rows, 128]`` statistics tile, ``[rows, 1]``."""
    lane = lax.broadcasted_iota(jnp.int32, ref_value.shape, 1)
    return jnp.sum(jnp.where(lane == lane_index, ref_value, 0.0), axis=1, keepdims=True)


def _scores(q, kb, attend, mask, scale):
    """One head's ``q k^T * scale`` over the row of keys, masked: ``[bq, s]``."""
    s = lax.dot_general(_only(q, mask), kb, _NT, preferred_element_type=jnp.float32)
    return jnp.where(attend, s * scale, _MASKED)


def _fwd_kernel(segq_ref, segk_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                *, head_dim: int):
    hb = pl.program_id(2)
    scale = head_dim ** -0.5
    masks = _head_masks(q_ref.shape[-1], head_dim)
    q = q_ref[0]
    attend = segq_ref[0] == segk_ref[0]  # [bq, 1] == [1, s]
    kb, vb = k_ref[0].astype(jnp.bfloat16), v_ref[0].astype(jnp.bfloat16)
    lane = lax.broadcasted_iota(jnp.int32, lse_ref.shape[1:], 1)
    # one lane a head, written a head block at a time: the first clears the rest
    lse = jnp.where(hb == 0, 0.0, lse_ref[0])
    outs = []
    for j, mask in enumerate(masks):
        s = _scores(q, kb, attend, mask, scale)
        m = jnp.max(s, axis=1, keepdims=True)
        e = jnp.exp(s - m)
        l = jnp.sum(e, axis=1, keepdims=True)
        p = (e * (1.0 / l)).astype(jnp.bfloat16)
        outs.append(jnp.dot(p, vb, preferred_element_type=jnp.float32))
        lse = jnp.where(lane == hb * len(masks) + j, m + jnp.log(l), lse)
    o_ref[0] = _by_head(outs, masks).astype(o_ref.dtype)
    lse_ref[0] = lse


def _bwd_kernel(segq_ref, segk_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                dq_ref, dk_ref, dv_ref, dk_sc, dv_sc,
                *, head_dim: int):
    hb, qi = pl.program_id(1), pl.program_id(2)
    scale = head_dim ** -0.5
    masks = _head_masks(q_ref.shape[-1], head_dim)

    @pl.when(qi == 0)
    def _():
        dk_sc[...] = jnp.zeros(dk_sc.shape, jnp.float32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, jnp.float32)

    q, do = q_ref[0], do_ref[0]
    attend = segq_ref[0] == segk_ref[0]
    qb, kb, vb, dob = (x.astype(jnp.bfloat16) for x in (q, k_ref[0], v_ref[0], do))
    lse_all = lse_ref[0]
    dqs, dks, dvs = [], [], []
    for j, mask in enumerate(masks):
        p = jnp.exp(_scores(q, kb, attend, mask, scale)
                    - _stat_lane(lse_all, hb * len(masks) + j))
        dp = lax.dot_general(_only(do, mask), vb, _NT, preferred_element_type=jnp.float32)
        # the row sum over this very P and dP: see the module's docstring
        ds = p * (dp - jnp.sum(p * dp, axis=1, keepdims=True)) * scale
        ds = ds.astype(jnp.bfloat16)
        dvs.append(lax.dot_general(p.astype(jnp.bfloat16), dob, _TN,
                                   preferred_element_type=jnp.float32))
        dks.append(lax.dot_general(ds, qb, _TN, preferred_element_type=jnp.float32))
        dqs.append(jnp.dot(ds, kb, preferred_element_type=jnp.float32))
    dq_ref[0] = _by_head(dqs, masks).astype(dq_ref.dtype)
    dk_sc[...] += _by_head(dks, masks)
    dv_sc[...] += _by_head(dvs, masks)

    @pl.when(qi == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _segments(segment_ids):
    seg = segment_ids.astype(jnp.int32)
    return seg[:, :, None], seg[:, None, :]  # a column a query, a row of keys


def _lane_block(head_dim: int) -> int:
    """Lanes a block: a head that is whole 128-lane tiles, else 128 lanes of heads."""
    return head_dim if head_dim % LANES == 0 else LANES


# jitted: the twelve layers of an encoder share one traced and lowered copy of
# each kernel instead of paying Mosaic's lowering at every call site
@functools.partial(jax.jit, static_argnames=("num_heads", "block_q", "interpret"))
def _forward(q, k, v, segment_ids, num_heads, block_q, interpret):
    b, s, hd = q.shape
    d = hd // num_heads
    w = _lane_block(d)
    segq, segk = _segments(segment_ids)
    q_spec = pl.BlockSpec((1, block_q, w), lambda bi, qi, hb: (bi, qi, hb))
    kv_spec = pl.BlockSpec((1, s, w), lambda bi, qi, hb: (bi, 0, hb))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, head_dim=d),
        grid=(b, s // block_q, hd // w),
        in_specs=[
            pl.BlockSpec((1, block_q, 1), lambda bi, qi, hb: (bi, qi, 0)),
            pl.BlockSpec((1, 1, s), lambda bi, qi, hb: (bi, 0, 0)),
            q_spec, kv_spec, kv_spec,
        ],
        # the lse block is revisited once a head block: that axis is innermost
        out_specs=[q_spec, pl.BlockSpec((1, block_q, LANES), lambda bi, qi, hb: (bi, qi, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, hd), q.dtype),
            jax.ShapeDtypeStruct((b, s, LANES), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_attention_fwd",
    )(segq, segk, q, k, v)


@functools.partial(jax.jit, static_argnames=("num_heads", "block_q", "interpret"))
def _backward(q, k, v, segment_ids, lse, do, num_heads, block_q, interpret):
    b, s, hd = q.shape
    d = hd // num_heads
    w = _lane_block(d)
    segq, segk = _segments(segment_ids)
    q_spec = pl.BlockSpec((1, block_q, w), lambda bi, hb, qi: (bi, qi, hb))
    kv_spec = pl.BlockSpec((1, s, w), lambda bi, hb, qi: (bi, 0, hb))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, head_dim=d),
        grid=(b, hd // w, s // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, 1), lambda bi, hb, qi: (bi, qi, 0)),
            pl.BlockSpec((1, 1, s), lambda bi, hb, qi: (bi, 0, 0)),
            q_spec, kv_spec, kv_spec, q_spec,
            pl.BlockSpec((1, block_q, LANES), lambda bi, hb, qi: (bi, qi, 0)),
        ],
        # dk, dv gather every query block's share: that axis is innermost
        out_specs=[q_spec, kv_spec, kv_spec],
        scratch_shapes=[pltpu.VMEM((s, w), jnp.float32), pltpu.VMEM((s, w), jnp.float32)],
        out_shape=[jax.ShapeDtypeStruct((b, s, hd), x.dtype) for x in (q, k, v)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_attention_bwd",
    )(segq, segk, q, k, v, do, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _attention(q, k, v, segment_ids, num_heads, block_q, interpret):
    o, _ = _forward(q, k, v, segment_ids, num_heads, block_q, interpret)
    return o


def _attention_fwd(q, k, v, segment_ids, num_heads, block_q, interpret):
    o, lse = _forward(q, k, v, segment_ids, num_heads, block_q, interpret)
    return o, (q, k, v, segment_ids, lse)


def _attention_bwd(num_heads, block_q, interpret, residuals, do):
    q, k, v, segment_ids, lse = residuals
    dq, dk, dv = _backward(q, k, v, segment_ids, lse, do, num_heads, block_q,
                           interpret)
    return dq, dk, dv, None


_attention.defvjp(_attention_fwd, _attention_bwd)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    segment_ids: jnp.ndarray,
    *,
    num_heads: int,
    block_q: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """``softmax(q k^T / sqrt(d)) v`` a head, each query over the keys of its
    own segment; differentiable in ``q, k, v``.

    q, k, v: ``[b, s, num_heads * d]``, heads side by side; segment_ids:
    ``[b, s]`` integers (the pad mask: real 1, pad 0). ``block_q`` defaults
    to the most queries whose tile stays within 1 MiB (512 at ``s`` = 512). Returns ``o`` in the shape and dtype of ``q``.
    The shape must pass :func:`supports`."""
    b, s, hd = q.shape
    d = hd // num_heads
    if not supports(s, num_heads, d):
        raise ValueError(f"flash_attention takes no [s={s}, heads={num_heads}, d={d}]")
    block_q = block_q or _block_q(s)
    if s % block_q or block_q % 8:
        raise ValueError(f"block_q {block_q} does not tile s={s}")
    return _attention(q, k, v, segment_ids, num_heads, block_q, interpret)
