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
   elsewhere) and gated SiLU in float32;
4. the chunk's gate-weighted rows go back onto their tokens as a product the
   MXU runs, not as a scatter-add (on the TPU a fixed cost of the target's
   shape, whatever the rows hold: 24 ms onto ``[8192, 7680]`` float32, PERF.md
   section 6, PR 34): for a block of sorted positions,
   ``onehot[token, position] @ rows`` *is* the scatter-add. The one-hot matrix
   is exact in bfloat16; the float32 rows go in as three bfloat16 addends
   (``hi + mid + lo``: 24 bits of significand, so their sum is the row itself)
   and the product accumulates in float32 — a token's result is still a
   float32 sum of float32 expert rows. A trip walks its chunk in blocks and
   stops after the last block that holds an assignment
   (:func:`combine_blocks`), so the work follows the assignments held, not
   ``rows``. **Where the held range is the router's whole width** (``whole``:
   no expert is absent, so every real token has exactly its ``k`` rows among
   the held) that product would be ``[t, 3 x 512] x [3 x 512, d]`` a block of
   512 of ``t x k`` positions — quadratic in the tokens (10.7 TFLOP a layer at
   16,384 tokens of width 2560, against the 1.4 the experts need; PERF.md
   section 7) — and the sort's inverse says where each token's rows lie: the
   trips write their rows into one ``[t x k, d]`` float32 buffer by sorted
   position and a token's result is the sum of the ``k`` rows gathered from
   it, float32 sums of float32 rows still, no product and no scatter. The
   gather walks the token rows in chunks of ``_GATHER_BLOCK``: a chunk in
   which no (token, choice) is an assignment (``choice >= 0``) could only
   gather the buffer's zero rows, so it is zeros without a gather — left
   padding makes the pads whole leading chunks, scattered pads cost what
   every row cost before (:func:`gather_slots` counts the token rows visited
   times ``k``). What decides a chunk is ``choice`` alone: a real token one of
   whose choices is -1 still has its chunk visited and misses that one row
   (its position sorts past the held, where the buffer holds zeros).

The count of rows handed to the grouped products (the sum of the group sizes
each call was given: rows outside a group are not computed) is returned
beside the result, so ``held - computed`` (the assignments dropped) is a
reading, not a claim.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["combine_blocks", "combined_positions", "gather_slots", "grouped_matmul",
           "held_expert_ffn"]

# megablox tiles (m, k, n) for one v5e core: [512, 1024] and [1024, 1024]
# bf16 operand tiles double-buffered plus a [512, 1024] f32 accumulator stay
# well inside the 16 MiB of scoped VMEM. The k and n of a call are cut by
# :func:`_tile`, at most this wide
_GMM_TILING = (512, 1024, 1024)

# sorted positions a block of the combine takes: [t, 3 x 512] one-hot columns
# against [3 x 512, d] addends, one pass over ``out`` a block
_COMBINE_BLOCK = 512

# token rows a chunk of the gather combine takes (``whole``): a chunk is the
# unit that is skipped where it holds no assignment
_GATHER_BLOCK = 512


def _tile(dim: int, cap: int) -> int:
    """The tile a dimension of ``dim`` is cut into, at most ``cap`` wide:
    ``cap`` itself while its partly filled last tile leaves no more than a
    tenth of the tiles' width empty (6144, 2048: none; 7680: 7.5 tiles, a
    sixteenth), else the widest multiple of 128 that divides ``dim`` (2560:
    2.5 tiles of 1024 would leave a sixth empty and mask every last tile of
    the reduction: 640, four whole tiles)."""
    if dim <= cap:
        return dim
    tiles = -(-dim // cap)
    if 10 * (tiles * cap - dim) <= tiles * cap:
        return cap
    return max((t for t in range(128, cap + 1, 128) if dim % t == 0), default=cap)


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
                   tiling=(tm, _tile(k, tk), _tile(n, tn)))
    return lax.ragged_dot(x, w, group_sizes, preferred_element_type=jnp.float32)


def combine_blocks(left, rows: int):
    """``(blocks, width)``: the combine of one trip walks ``blocks`` blocks of
    ``width`` sorted positions when ``left`` positions from the trip's start
    on still hold an assignment — up to the last block that holds one."""
    width = _COMBINE_BLOCK if rows % _COMBINE_BLOCK == 0 else rows
    return -(-jnp.clip(left, 0, rows) // width), width


def combined_positions(n_held, rows: int):
    """Sorted positions the combine visits (its blocks times their width,
    summed over the loop's trips) when ``n_held`` assignments are consumed
    ``rows`` at a time: every trip but the last is full and walked whole."""
    last, width = combine_blocks(n_held % rows, rows)
    return n_held // rows * rows + last * width


def _gather_chunks(choice: jnp.ndarray):
    """``(live [chunks] bool, width)``: the gather combine walks ``choice``'s
    ``t`` token rows in chunks of ``width``; a chunk is live where any of its
    (token, choice) holds an assignment (``choice >= 0``)."""
    t, k = choice.shape
    width = _GATHER_BLOCK if t % _GATHER_BLOCK == 0 else t
    return jnp.any((choice >= 0).reshape(t // width, width * k), axis=1), width


def gather_slots(choice: jnp.ndarray):
    """Rows the gather combine reads for ``choice`` [t, k] (``whole``): the
    token rows of its live chunks times ``k`` — the assignments over it is how
    full the visited chunks were."""
    live, width = _gather_chunks(choice)
    return jnp.sum(live, dtype=jnp.int32) * (width * choice.shape[1])


def _add_rows(out: jnp.ndarray, tok: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """``out.at[tok].add(y)`` as one product: ``onehot[t, 3w] @ [hi; mid; lo]``.

    out: [t, d] float32; tok: [w] token of each row; y: [w, d] float32 (finite:
    a row that holds no assignment is zero). ``reduce_precision`` and not a
    cast to bfloat16 and back, which XLA may drop as excess precision."""
    hi = lax.reduce_precision(y, exponent_bits=8, mantissa_bits=7)
    mid = lax.reduce_precision(y - hi, exponent_bits=8, mantissa_bits=7)
    addends = jnp.concatenate([hi, mid, y - hi - mid]).astype(jnp.bfloat16)
    onehot = jnp.arange(out.shape[0], dtype=jnp.int32)[:, None] == jnp.tile(tok, 3)[None, :]
    return out + jnp.dot(onehot.astype(jnp.bfloat16), addends,
                         preferred_element_type=jnp.float32)


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
    activation=jax.nn.silu,
    whole: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``sum over (choice in held) of gate * W_down(act(W_gate u) * (W_up u))``.

    u: [t, d] tokens; choice: [t, k] global expert ids (anything outside
    ``[lo, lo + n)`` is not this chip's: absent, zero-compute, or -1 for a pad
    token); gates: [t, k] float32; w_gate / w_up: [n, d, f]; w_down:
    [n, f, d]; ``rows``: assignments consumed per loop iteration;
    ``activation``: the gate's, float32 in and out; ``whole``: the caller's
    word that ``[lo, lo + n)`` is everything the router can choose (every
    ``choice >= 0`` is held), which picks the gather for the combine (module
    docstring, step 4), taken over the chunks of ``_GATHER_BLOCK`` token rows
    that hold a ``choice >= 0``; the others are zeros.

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

    def trip(start):
        """The sorted positions ``[start, start + rows)``: their tokens, their
        gate-weighted rows (zero where a position holds no assignment) and the
        group sizes the products were given."""
        pos = start + offs
        valid = pos < n_held
        pos = jnp.minimum(pos, a - 1)
        tok = token[pos]
        x = u[tok]
        # the part of each expert's run of rows that falls inside this chunk
        sz = jnp.clip(ends - start, 0, rows) - jnp.clip(ends - sizes - start, 0, rows)
        h = activation(grouped_matmul(x, w_gate, sz)) * grouped_matmul(x, w_up, sz)
        y = grouped_matmul(h.astype(u.dtype), w_down, sz)
        return tok, jnp.where(valid[:, None], y * gate[pos][:, None], 0.0), sz

    def chunk(carry):
        start, out, computed = carry
        tok, y, sz = trip(start)
        # the rows back onto their tokens, block by block as far as the chunk
        # holds assignments: float32 sums by a product the MXU runs, where a
        # scatter-add costs the TPU the same whatever the chunk holds (module
        # docstring, step 4)
        blocks, width = combine_blocks(n_held - start, rows)
        with jax.named_scope("combine"):
            out = lax.fori_loop(0, blocks, lambda j, acc: _add_rows(
                acc, lax.dynamic_slice_in_dim(tok, j * width, width),
                lax.dynamic_slice_in_dim(y, j * width, width)), out)
        return start + rows, out, computed + jnp.sum(sz, dtype=jnp.int32)

    def store(carry):
        start, buf, computed = carry
        _, y, sz = trip(start)
        return (start + rows, lax.dynamic_update_slice_in_dim(buf, y, start, 0),
                computed + jnp.sum(sz, dtype=jnp.int32))

    zero = jnp.zeros((), jnp.int32)
    if not whole:
        _, out, computed = lax.while_loop(
            lambda c: c[0] < n_held, chunk, (zero, jnp.zeros((t, d), jnp.float32), zero))
        return out, computed
    # every real token's k rows are among the held: the trips leave their rows
    # by sorted position (whole trips: the buffer is ``rows`` longer than the
    # assignments at most; what no trip reaches stays zero, and a pad's
    # choices sort there), and each token sums its own k
    _, buf, computed = lax.while_loop(
        lambda c: c[0] < n_held, store,
        (zero, jnp.zeros((-(-a // rows) * rows, d), jnp.float32), zero))
    with jax.named_scope("combine"):
        at = jnp.argsort(order).astype(jnp.int32).reshape(t, k)  # the sort's inverse
        live, width = _gather_chunks(choice)

        def gathered(c):
            """Token rows ``[c * width, (c + 1) * width)``: each one's ``k``
            rows, summed in the order of its choices."""
            at_c = lax.dynamic_slice_in_dim(at, c * width, width)
            acc = buf[at_c[:, 0]]
            for j in range(1, k):
                acc = acc + buf[at_c[:, j]]
            return acc

        # a chunk none of whose choices is an assignment (left pads) reads
        # zero rows only: it is zeros without the gathers
        out = lax.map(lambda c: lax.cond(
            live[c], gathered, lambda _: jnp.zeros((width, d), jnp.float32), c),
            jnp.arange(t // width, dtype=jnp.int32))
    return out.reshape(t, d), computed
