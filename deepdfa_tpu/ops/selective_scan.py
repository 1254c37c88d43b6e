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

**Two forms of one algorithm.** A mixer's ``scan`` scope is the softplus, the
scan and the gate. On one TPU device, at a shape :func:`supports` takes, that
is one Pallas kernel behind :func:`gated_scan` (``ops/selective_scan_kernel.py``,
imported where it first runs: Pallas costs a second of imports) whose float32
state stays on the chip over the positions and which reads and writes the
step's own arrays; the model's ``ssm`` counts say so (``fused``). Everywhere
else — the CPU, several devices, other shapes — and for every gradient it is
the plain form below, which the kernel is held to. Chunks of 128 positions
were chosen on the chip at ``[4, 2048, 5120]`` x 16 states under the cell's
padding (PERF.md section 6): 0.94 / 0.90 / 0.95 / 1.05 ms a layer at 64 / 128 /
256 / 512 (1.94 with no padding), where the plain form takes 5.55.

The plain form is ``lax.scan`` over the positions carrying the ``[b, n, d]``
float32 state (channels on the lanes), :data:`UNROLL` positions a trip. The state and its
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

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["causal_conv1d", "selective_scan", "gated_scan", "supports", "UNROLL"]

UNROLL = 32  # positions a trip of the loop
# what the kernel takes: states, channels a group (one register a state index), positions a
# grid step (the largest that tiles the sequence)
D_STATE, GROUP, CHUNKS = 16, 1024, (128, 64, 32, 16)


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


def supports(seq_len: int, d_inner: int, d_state: int) -> bool:
    """Whether the kernel takes this shape: 16 states, channels in whole
    groups of 1,024 (a state index of a group is one register), whole chunks
    of positions."""
    return d_state == D_STATE and d_inner % GROUP == 0 and seq_len % CHUNKS[-1] == 0


def _plain(c, dt, dt_bias, A, B, C, D, z, mask):
    delta = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
    y = selective_scan(c, delta, A, B, C, D, mask)
    z = z[..., z.shape[-1] - c.shape[-1]:]
    return (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))).astype(c.dtype)


def _forward(*operands_mask, chunk, interpret):
    from deepdfa_tpu.ops.selective_scan_kernel import scan_forward

    return scan_forward(*operands_mask, chunk=chunk, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10))
def _fused(c, dt, dt_bias, A, B, C, D, z, mask, chunk, interpret):
    return _forward(c, dt, dt_bias, A, B, C, D, z, mask, chunk=chunk, interpret=interpret)


def _fused_fwd(*args):
    return _forward(*args[:9], chunk=args[9], interpret=args[10]), args[:9]


def _fused_bwd(chunk, interpret, residuals, do):
    *operands, mask = residuals
    _, vjp = jax.vjp(lambda *xs: _plain(*xs, mask), *operands)
    return (*vjp(do), None)


_fused.defvjp(_fused_fwd, _fused_bwd)


def gated_scan(c: jnp.ndarray, dt: jnp.ndarray, dt_bias: jnp.ndarray, A: jnp.ndarray,
               B: jnp.ndarray, C: jnp.ndarray, D: jnp.ndarray, z: jnp.ndarray,
               mask: jnp.ndarray | None = None, *, interpret: bool | None = None,
               chunk: int | None = None) -> jnp.ndarray:
    """``selective_scan(c, softplus(dt + dt_bias), ..) * silu(z)``, [b, s, d]
    in ``c``'s dtype: a Mamba mixer between ``dt_proj`` and ``out_proj``. dt:
    [b, s, d]; dt_bias: [d]; z: [b, s, d], or a wider array whose last ``d``
    columns it is (``in_proj``'s whole output: a slice handed to a kernel is a
    copy, so the kernel reads those columns where they lie); the rest as
    :func:`selective_scan`'s.

    ``interpret=None`` is the plain form. ``False`` / ``True`` is the kernel,
    compiled / under the Pallas interpreter, ``chunk`` positions a grid step
    (the largest of :data:`CHUNKS` that tiles ``s`` if not given); the shape
    must pass :func:`supports`. Its real tokens are the plain form's to
    float32 rounding under any mask; at a pad position it returns zeros where
    the whole chunk lies before the row's first real token and the plain
    form's value elsewhere (nothing reads either). Differentiable: the
    backward is the plain form's, recomputed."""
    if interpret is None:
        return _plain(c, dt, dt_bias, A, B, C, D, z, mask)
    (b, s, d), n = c.shape, A.shape[1]
    if not supports(s, d, n) or (z.shape[-1] - d) % GROUP:
        raise ValueError(f"the selective-scan kernel takes no [s={s}, d_inner={d}, d_state={n}, "
                         f"z={z.shape[-1]}]")
    chunk = chunk or next(ch for ch in CHUNKS if s % ch == 0)
    if s % chunk or chunk % CHUNKS[-1]:
        raise ValueError(f"chunks of {chunk} positions do not tile s={s}")
    if mask is None:
        mask = jnp.ones((b, s), bool)
    return _fused(c, dt, dt_bias, A, B, C, D, z, mask, chunk, interpret)
