"""The Mamba-1 selective scan with its state on the chip — a Pallas TPU kernel,
forward only, behind ``ops/selective_scan.gated_scan``.

One call does what the mixer's ``scan`` scope does (``selective_scan``'s
module docstring has the recurrence)::

    delta = softplus(dt + dt_bias) ; x = delta * c (0 at a pad)
    s_t = exp(delta_t * A) * s_{t-1} + x_t (x) B_t ;  y_t = s_t . C_t + D * c_t
    out = y * silu(z)

reading ``c``, ``dt``, ``z`` ``[b, s, d]`` as the products left them (the
decoder's dtype, channels along the lanes) and writing ``out`` the same way:
nothing float32 of that size passes through HBM. Grid ``(batch, groups of
1,024 channels, chunks of positions)``, the chunks last and sequential; the
float32 state of a group lives on the chip from a row's first chunk to its
last (in registers over a chunk's positions, in VMEM between chunks).

**Layout inside a chunk.** The arrays arrive with positions along the
sublanes, which is right for everything that is elementwise (the upcasts,
softplus, ``delta * c``, the mask, ``D * c``, the gate: whole ``[chunk, 128]``
tiles), and wrong for the recurrence, which wants one position's channels to
fill a register. Between the two stands a float32 scratch written with a
sublane stride of 8: ``delta`` and ``x`` of the chunk's 128-lane column ``g``
go to rows ``g, g + 8, ..`` of it, so rows ``8 t .. 8 t + 7`` hold position
``t``'s 1,024 channels as one ``[8, 128]`` tile; ``y`` comes back through the
same stride. The store and load units do that relayout; no arithmetic slot is
spent on it. A state is then 16 such tiles (one a state index ``n``), carried
in registers over the chunk's positions; ``B_t[n]`` and ``C_t[n]`` are scalars
from SMEM, a position's 32 side by side. Which channel lands on which sublane
is the kernel's own business: ``A`` is handed over in the same arrangement
(and times ``log2 e``, so that ``exp(delta A)`` is the chip's own power of two
of one float32 product). The recurrence fills the four vector slots of the
v5e's bundles: 37.75 bundles a position by the compiler's schedule, 96
multiplies and adds, 16 powers and 32 scalar-to-vector moves.

**Leading pads are skipped, any mask is exact.** ``first[b]`` (scalar
prefetch) is a row's first real position. A chunk wholly before it is neither
fetched (its blocks' indices are the first visited chunk's) nor computed: the
state there is exactly 0, as the plain form computes it, and ``out`` is zeros.
Inside a visited chunk ``x`` is zeroed at each pad by the mask itself, so a
pad adds nothing to the state and decays it as the plain form does.

Same arithmetic as the plain form: every upcast happens in VMEM and softplus,
``exp(delta A)``, the state and its recurrence, the ``C`` reduction, ``D * c``
and the gate are float32; ``y`` is rounded to the operands' dtype before the
gate and ``out`` after it, where the plain form rounds them (XLA keeps that
cast pair in the step it compiles for the chip).

``interpret=True`` runs the same kernel under the Pallas interpreter (CPU
tests). The event on the device's ``XLA Ops`` line is ``selective_scan_fwd``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepdfa_tpu.ops.selective_scan import D_STATE, GROUP

__all__ = ["scan_forward", "first_real"]

LANES, SUB = 128, 8  # a register: ``GROUP`` = 1,024 channels' state for one ``n``
_LOG2E = 1.4426950408889634  # exp(delta A) = 2 ** (delta * (A log2 e)): the EUP's own power


def first_real(mask: jnp.ndarray) -> jnp.ndarray:
    """``[b]`` int32: a row's first real position (``s`` where it has none)."""
    s = mask.shape[1]
    return jnp.where(mask.any(axis=1), jnp.argmax(mask, axis=1), s).astype(jnp.int32)


def _softplus(v):
    """``jax.nn.softplus`` (``logaddexp(v, 0)``) less its select on a NaN
    difference: ``v - 0`` has none that ``v`` had not."""
    return jnp.maximum(v, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(v)))


def _kernel(first_ref, bc_ref, mask_ref, c_ref, dt_ref, z_ref, bias_ref, d_ref, a_ref,
            o_ref, state_sc, delta_sc, x_sc, y_sc, *, unroll: int):
    bi, si = pl.program_id(0), pl.program_id(2)
    chunk = c_ref.shape[1]
    f32 = lambda v: v.astype(jnp.float32)
    # 128-lane column ``g`` of the blocks <-> rows ``g, g + 8, ..`` of the scratch
    column = lambda g: slice(g * LANES, (g + 1) * LANES)
    strided = lambda g: pl.ds(g, chunk, stride=SUB)

    @pl.when(si == 0)
    def _():
        state_sc[...] = jnp.zeros(state_sc.shape, jnp.float32)

    visited = (si + 1) * chunk > first_ref[bi]

    @pl.when(jnp.logical_not(visited))  # leading pads alone: the state stays 0
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(visited)
    def _():
        real = mask_ref[0] != 0  # [chunk, 1]
        for g in range(SUB):
            delta = _softplus(f32(dt_ref[0, :, column(g)]) + bias_ref[:, column(g)])
            delta_sc[strided(g), :] = delta
            x_sc[strided(g), :] = jnp.where(real, delta * f32(c_ref[0, :, column(g)]), 0.0)

        a = [a_ref[0, n] for n in range(D_STATE)]

        def positions(i, state):
            state = list(state)
            for u in range(unroll):
                t = i * unroll + u
                rows = pl.ds(pl.multiple_of(t * SUB, SUB), SUB)
                if u % 4 == 0:  # an SMEM window is whole 128-word tiles: four positions' scalars
                    bc = bc_ref.at[0, 0, pl.ds(pl.multiple_of(t * 2 * D_STATE, LANES), LANES)]
                b_t, c_t = u % 4 * 2 * D_STATE, u % 4 * 2 * D_STATE + D_STATE
                delta, x = delta_sc[rows, :], x_sc[rows, :]
                y = None
                for n in range(D_STATE):
                    state[n] = jnp.exp2(delta * a[n]) * state[n] + x * bc[b_t + n]
                    term = state[n] * bc[c_t + n]
                    y = term if y is None else y + term
                y_sc[rows, :] = y
            return tuple(state)

        state = lax.fori_loop(0, chunk // unroll, positions,
                              tuple(state_sc[n] for n in range(D_STATE)))
        for n in range(D_STATE):
            state_sc[n] = state[n]

        for g in range(SUB):
            y = y_sc[strided(g), :] + d_ref[:, column(g)] * f32(c_ref[0, :, column(g)])
            y = f32(y.astype(o_ref.dtype))  # ``selective_scan`` hands ``y`` over in ``c``'s dtype
            gate = jax.nn.silu(f32(z_ref[0, :, column(g)]))
            o_ref[0, :, column(g)] = (y * gate).astype(o_ref.dtype)


# jitted: a decoder's Mamba layers share one traced and lowered copy
@functools.partial(jax.jit, static_argnames=("chunk", "unroll", "interpret"))
def scan_forward(c, dt, dt_bias, A, B, C, D, z, mask, *, chunk: int, unroll: int = 8,
                 interpret: bool = False):
    """``out`` [b, s, d] of the module docstring, ``c``'s dtype. c, dt:
    [b, s, d]; z: the last ``d`` columns of [b, s, >= d]; dt_bias, D: [d]; A:
    [d, 16]; B, C: [b, s, 16]; mask: [b, s] bool. ``chunk`` positions a grid
    step (whole sublane tiles of ``c``'s dtype, and whole trips of ``unroll``
    positions, itself a multiple of 4); ``d`` and ``z``'s offset whole groups
    of 1,024 channels. ``out`` at a pad position: zeros in a skipped chunk,
    else what the recurrence gives with nothing added to the state."""
    b, s, d = c.shape
    f32 = lambda v: v.astype(jnp.float32)
    # [d, 16] -> [d / 1024, 16, 8, 128]: state ``n`` of a group's channels as one register
    a = (f32(A) * _LOG2E).reshape(d // GROUP, SUB, LANES, D_STATE).transpose(0, 3, 1, 2)
    # B_t[n], C_t[n] as scalars: [b, 1, s * 32] float32 in SMEM, a position's 32 side by side
    bc = f32(jnp.concatenate([B, C], axis=-1)).reshape(b, 1, s * 2 * D_STATE)
    # a chunk of leading pads asks for the first visited chunk's blocks: nothing is fetched
    at = lambda si, first, bi: jnp.maximum(si, jnp.minimum(first[bi] // chunk, s // chunk - 1))
    z_at = (z.shape[-1] - d) // GROUP  # ``z`` read where it lies in ``in_proj``'s output
    tile = lambda skip=0: pl.BlockSpec(
        (1, chunk, GROUP), lambda bi, ci, si, first: (bi, at(si, first, bi), skip + ci))
    row = pl.BlockSpec((1, GROUP), lambda bi, ci, si, first: (0, ci))
    scratch = pltpu.VMEM((chunk * SUB, LANES), jnp.float32)
    return pl.pallas_call(
        functools.partial(_kernel, unroll=unroll),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, d // GROUP, s // chunk),
            in_specs=[
                pl.BlockSpec((1, 1, 2 * D_STATE * chunk),
                             lambda bi, ci, si, first: (bi, 0, at(si, first, bi)),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((1, chunk, 1), lambda bi, ci, si, first: (bi, at(si, first, bi), 0)),
                tile(), tile(), tile(z_at), row, row,
                pl.BlockSpec((1, D_STATE, SUB, LANES), lambda bi, ci, si, first: (ci, 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, chunk, GROUP), lambda bi, ci, si, first: (bi, si, ci)),
            scratch_shapes=[pltpu.VMEM((D_STATE, SUB, LANES), jnp.float32),
                            scratch, scratch, scratch],
        ),
        out_shape=jax.ShapeDtypeStruct((b, s, d), c.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="selective_scan_fwd",
    )(first_real(mask), bc, mask.astype(jnp.int32)[..., None], c, dt, z,
      f32(dt_bias)[None], f32(D)[None], a)
