"""Degree-2 power retention with its state on the chip — a Pallas TPU kernel,
forward only, behind ``ops/power_retention.power_retention``.

Grid ``(row, key/value head, chunk)``, the chunks last and sequential. One grid
step serves a key/value head's whole group of query heads (five at 40 | 8)
over one chunk of ``C`` positions: it reads the chunk's ``q`` ``[C, rep x
128]``, ``k`` and ``v`` ``[C, 128]`` as the projections left them (heads side
by side, the decoder's dtype) and writes ``o`` the same way, so nothing of the
feature expansion passes through HBM. The head's float32 state ``[S | z]``
stays in VMEM from the row's first visited chunk to its last.

**The feature map in 65 lane tiles, by diagonal.** ``phi(x)`` (``x_a x_b``,
``a <= b``) has 8,256 entries at ``d = 128``. Tile ``a`` (``a = 0 .. 64``) is
``x * roll(x, a)``: lane ``l`` holds ``x_l x_{(l + a) % 128}``, the pairs at
circular distance ``a``. With ``p_l = q_l k_l``, ``(q . k)^2 = sum_delta sum_l
p_l p_{l + delta}`` over all 128 offsets, and offsets ``delta`` and ``128 -
delta`` give the same sum, which is tile ``delta`` of ``q`` dotted with tile
``delta`` of ``k``; so the coefficients are 1 on tile 0, 2 on tiles 1 .. 63
and 1 on tile 64, over ``d``, put on the key side only. Tiles 1 .. 63 hold each
pair ``a < b`` once (``b - a`` is the tile or 128 less it), tile 0 the squares,
and tile 64 each of its 64 pairs twice (lanes ``l`` and ``l + 64``), where one
copy and 64 zeros would do: 65 tiles, 8,320 lanes, 0.8% over the needed width.
A tile is one lane rotation (``pltpu.roll``) and one product, built in VMEM per
chunk (float32; rounded to bfloat16 for the MXU), 13 tiles a trip of a loop
(the 65 unrolled took Mosaic 26 s to compile, the loop 2 s). The symmetric
``[128, 128, 128]`` form (twice the operations) is not used.

**Per chunk and head group** (``G`` the log-gates summed from the chunk's
start, ``QS`` the group's query heads stacked ``[rep C, 128]``)::

    P     = (QS K^T)^2 / d * exp(G_t - G_j) * causal * real_j     [rep C, C]
    num   = P V + exp(G_t) * sum_a phi_a(QS) S_a                   65 products [rep C, 128] x [128, 128]
    den   = rowsum(P) + exp(G_t) * sum_a rowsum(phi_a(QS) * z_a)   on the VPU, float32
    o     = num / (den + eps)
    S     = exp(G_C) S + phi(K)^T (exp(G_C - G_j) * real_j * V)     one product [8320, C] x [C, 128]
    z_a   = exp(G_C) z_a + colsum(exp(G_C - G_j) * real_j * phi_a(K))

``S`` is ``[8320, 128]`` float32 (4.3 MB), ``z`` one row of 128 lanes a tile.
Products take bfloat16 operands and accumulate in float32 (the state is
rounded to bfloat16 as an operand of the read, and kept float32); the
gates, ``P``, the denominators and the ratio are float32.

**Leading pads are skipped, any mask is exact.** ``first[b]`` (scalar
prefetch) is a row's first real position. A chunk wholly before it is neither
fetched (its blocks' indices are the first visited chunk's) nor computed: the
state there is exactly 0, and ``o`` is zeros. Inside a visited chunk a pad's
weight is 0 by the mask itself and its log-gate 0, so it adds nothing to the
state and decays nothing.

``interpret=True`` runs the same kernel under the Pallas interpreter (CPU
tests). The event on the device's ``XLA Ops`` line is ``power_retention_fwd``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepdfa_tpu.ops.power_retention import EPS, HEAD_DIM, _chunk_gates
from deepdfa_tpu.ops.selective_scan_kernel import first_real

__all__ = ["retention_forward"]

LANES = HEAD_DIM
TILES = LANES // 2 + 1  # 65 lane tiles hold phi's 8,256 entries
TRIP = 13  # tiles a trip of the loop over them (Mosaic unrolls a loop wholly or not at all)
_VMEM_LIMIT = 64 * 1024 * 1024  # the state (4.3 MB), phi(K) (2.1 MB at C = 128) and the blocks


def _phi_tile(x: jnp.ndarray, a) -> jnp.ndarray:
    """Tile ``a`` of phi (module docstring, no factors) of the rows of ``x``
    [M, 128] float32: lane ``l`` holds ``x_l x_{(l + a) % 128}``. ``a`` may be
    traced: the rotation takes it as its shift."""
    return x * pltpu.roll(x, (LANES - a) % LANES, 1)


def _tile_coef(a):
    """Tile ``a``'s factor on the key side: 1 on tiles 0 and 64, 2 on the rest,
    over ``d``."""
    return jnp.where((a == 0) | (a == LANES // 2), 1.0, 2.0) * (1.0 / LANES)


def _kernel(first_ref, last_ref, q_ref, k_ref, v_ref, gcol_ref, grow_ref, mcol_ref, mrow_ref,
            o_ref, s_ref, z_ref, pk_ref, *, rep: int):
    bi, gi, si = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    chunk = k_ref.shape[1]
    d = LANES
    f32, bf16 = jnp.float32, jnp.bfloat16

    @pl.when(si == 0)
    def _():
        s_ref[...] = jnp.zeros(s_ref.shape, f32)
        z_ref[...] = jnp.zeros(z_ref.shape, f32)

    visited = (si + 1) * chunk > first_ref[bi]

    @pl.when(jnp.logical_not(visited))  # leading pads alone: the state stays 0
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(visited)
    def _():
        # the group's query heads stacked along the rows: [rep C, d]
        qs = jnp.concatenate([q_ref[0, :, h * d:(h + 1) * d] for h in range(rep)],
                             axis=0).astype(bf16)
        k, v = k_ref[0].astype(bf16), v_ref[0].astype(bf16)  # [C, d]
        g_col, g_row = gcol_ref[0, 0], grow_ref[0, 0]  # [C, 1], [1, C]
        real_col, real_row = mcol_ref[0] > 0, mrow_ref[0] > 0  # [C, 1], [1, C]
        g_last = last_ref[(bi * pl.num_programs(1) + gi) * pl.num_programs(2) + si]  # the chunk's decay

        # within the chunk: the scores squared, decayed, causal, pad keys out
        t = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        ok = (j <= t) & real_row
        decay = jnp.where(ok, jnp.exp(jnp.minimum(g_col - g_row, 0.0)), 0.0)  # [C, C]
        decay = jnp.concatenate([decay] * rep, axis=0)
        scores = jax.lax.dot_general(qs, k, (((1,), (1,)), ((), ())), preferred_element_type=f32)
        p = jnp.square(scores) * (decay * (1.0 / d))  # [rep C, C]
        num = jnp.dot(p.astype(bf16), v, preferred_element_type=f32)
        den = jnp.sum(p, axis=1, keepdims=True)

        # from the chunks before: phi(QS) against the state, tile by tile
        qf, kf = qs.astype(f32), k.astype(f32)
        w_col = jnp.where(real_col, jnp.exp(g_last - g_col), 0.0)  # [C, 1]: into the state
        keep = jnp.exp(jnp.full((1, d), g_last, f32))  # [1, d]

        def tile(a, acc, dacc):
            pq = _phi_tile(qf, a)
            at = pl.multiple_of(a * d, d)
            acc += jnp.dot(pq.astype(bf16), s_ref[pl.ds(at, d), :].astype(bf16),
                           preferred_element_type=f32)
            z_a = z_ref[pl.ds(a, 1), :]
            dacc += pq * z_a
            pk = _phi_tile(kf, a) * _tile_coef(a)  # [C, d]
            pk_ref[:, pl.ds(at, d)] = pk.astype(bf16)
            z_ref[pl.ds(a, 1), :] = keep * z_a + jnp.sum(w_col * pk, axis=0, keepdims=True)
            return acc, dacc

        def trip(i, carry):  # TRIP tiles a trip: the scheduler overlaps one's VPU with another's MXU
            for u in range(TRIP):
                carry = tile(i * TRIP + u, *carry)
            return carry

        zeros = jnp.zeros((rep * chunk, d), f32)
        acc, dacc = jax.lax.fori_loop(0, TILES // TRIP, trip, (zeros, zeros))
        into = jnp.concatenate([jnp.exp(g_col)] * rep, axis=0)  # [rep C, 1]
        num += into * acc
        den += into * jnp.sum(dacc, axis=1, keepdims=True)
        o = num / (den + EPS)
        for h in range(rep):
            o_ref[0, :, h * d:(h + 1) * d] = o[h * chunk:(h + 1) * chunk].astype(o_ref.dtype)

        vd = (v.astype(f32) * w_col).astype(bf16)  # [C, d]
        s_ref[...] = keep * s_ref[...] + jax.lax.dot_general(
            pk_ref[...], vd, (((0,), (0,)), ((), ())), preferred_element_type=f32)


# jitted: a decoder's layers share one traced and lowered copy
@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def retention_forward(q, k, v, log_g, mask, *, chunk: int, interpret: bool = False):
    """``o`` [b, s, h * 128] in ``v``'s dtype (``ops/power_retention``'s module
    docstring). q: [b, s, h * 128]; k, v: [b, s, hk * 128]; log_g: [b, s, hk];
    mask: [b, s] bool. ``chunk`` positions a grid step (a multiple of 8 that
    tiles ``s``). ``o`` at a pad position: zeros in a skipped chunk, else what
    the ratio gives there (finite, unread)."""
    b, s, hkd = k.shape
    hk = log_g.shape[-1]
    rep = q.shape[-1] // hkd
    d = LANES
    g = jnp.swapaxes(_chunk_gates(log_g, mask, chunk), 1, 2)  # [b, hk, s]
    real = mask.astype(jnp.float32)
    n = s // chunk
    # a chunk of leading pads asks for the first visited chunk's blocks: nothing is fetched
    at = lambda si, first, bi: jnp.maximum(si, jnp.minimum(first[bi] // chunk, n - 1))
    spec = lambda width: pl.BlockSpec(
        (1, chunk, width), lambda bi, gi, si, first, _: (bi, at(si, first, bi), gi))
    return pl.pallas_call(
        functools.partial(_kernel, rep=rep),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hk, n),
            in_specs=[
                spec(rep * d), spec(d), spec(d),
                pl.BlockSpec((1, 1, chunk, 1),
                             lambda bi, gi, si, first, _: (bi, gi, at(si, first, bi), 0)),
                pl.BlockSpec((1, 1, 1, chunk),
                             lambda bi, gi, si, first, _: (bi, gi, 0, at(si, first, bi))),
                pl.BlockSpec((1, chunk, 1), lambda bi, gi, si, first, _: (bi, at(si, first, bi), 0)),
                pl.BlockSpec((1, 1, chunk), lambda bi, gi, si, first, _: (bi, 0, at(si, first, bi))),
            ],
            out_specs=pl.BlockSpec((1, chunk, rep * d), lambda bi, gi, si, first, _: (bi, si, gi)),
            scratch_shapes=[pltpu.VMEM((TILES * d, d), jnp.float32),
                            pltpu.VMEM((-(-TILES // 8) * 8, d), jnp.float32),
                            pltpu.VMEM((chunk, TILES * d), jnp.bfloat16)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="power_retention_fwd",
    )(first_real(mask), g.reshape(b, hk, n, chunk)[..., -1].reshape(-1), q, k, v, g[..., None], g[:, :, None, :],
      real[..., None], real[:, None, :])
