"""The combine of ``ops/grouped.py:held_expert_ffn`` — the held experts' rows
back onto their tokens as a one-hot product — against the scatter-add it
replaced (written here), in float32; the blocks it walks against
``combined_positions``, the count the ``moe`` stats carry; and where every
expert is held (``whole``) the gather over the chunks of token rows that hold
an assignment against both, the chunks it visits against ``gather_slots``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from deepdfa_tpu.ops import grouped
from deepdfa_tpu.ops.grouped import (
    combine_blocks,
    combined_positions,
    gather_slots,
    grouped_matmul,
    held_expert_ffn,
)


def scatter_add_ffn(u, choice, gates, w_gate, w_up, w_down, *, lo, rows):
    """The same sort, loop and grouped products; each chunk's rows added onto
    their tokens one index after the other."""
    t, d = u.shape
    k, n = choice.shape[1], w_gate.shape[0]
    local = choice.reshape(-1) - lo
    key = jnp.where((local >= 0) & (local < n), local, n).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(n)[None, :], axis=0, dtype=jnp.int32)
    ends = jnp.cumsum(sizes)
    n_held = int(ends[-1])
    token, gate = (order // k).astype(jnp.int32), gates.reshape(-1)[order]
    out = jnp.zeros((t, d), jnp.float32)
    for start in range(0, n_held, rows):
        pos = jnp.minimum(start + jnp.arange(rows), t * k - 1)
        valid = start + jnp.arange(rows) < n_held
        tok = token[pos]
        sz = jnp.clip(ends - start, 0, rows) - jnp.clip(ends - sizes - start, 0, rows)
        x = u[tok]
        h = jax.nn.silu(grouped_matmul(x, w_gate, sz)) * grouped_matmul(x, w_up, sz)
        y = grouped_matmul(h.astype(u.dtype), w_down, sz)
        y = jnp.where(valid[:, None], y * gate[pos][:, None], 0.0)
        out = out.at[jnp.where(valid, tok, t)].add(y, mode="drop")
    return out, n_held


def _weights(rng, n, d, f, dtype=jnp.float32):
    wg, wu = (jnp.asarray(rng.normal(size=(n, d, f)), dtype) for _ in range(2))
    return wg, wu, jnp.asarray(rng.normal(size=(n, f, d)), dtype)


def _positions(choice, lo, n):
    """Sorted position of every held (token, slot), -1 elsewhere: numpy's
    stable sort by local expert, as the function sorts."""
    local = np.asarray(choice).reshape(-1) - lo
    key = np.where((local >= 0) & (local < n), local, n)
    order = np.argsort(key, kind="stable")
    at = np.full(local.shape, -1)
    at[order[: int((key < n).sum())]] = np.arange(int((key < n).sum()))
    return at.reshape(np.asarray(choice).shape)


def _same(got, want):
    scale = float(jnp.abs(want).max()) or 1.0
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6 * scale)


# what a choice is drawn from: held experts are [3, 7); -1 a pad; 0-2 and 7-11 absent
DRAWS = {
    "pads and absent experts mixed in": lambda rng, t, k: rng.integers(-1, 12, size=(t, k)),
    "every assignment held": lambda rng, t, k: rng.integers(3, 7, size=(t, k)),
    "no assignment held": lambda rng, t, k: rng.choice([-1, 0, 2, 7, 11], size=(t, k)),
}


@pytest.mark.parametrize("rows", [8, 32, 4096])
@pytest.mark.parametrize("draw", list(DRAWS))
def test_combine_equals_the_scatter_add(draw, rows):
    rng = np.random.default_rng(rows)
    t, k, d, f = 40, 3, 16, 8
    u = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    choice = jnp.asarray(DRAWS[draw](rng, t, k), jnp.int32)
    gates = jnp.asarray(rng.random((t, k)), jnp.float32)
    w = _weights(rng, 4, d, f)
    out, computed = jax.jit(lambda *a: held_expert_ffn(*a, lo=3, rows=rows))(
        u, choice, gates, *w)
    want, n_held = scatter_add_ffn(u, choice, gates, *w, lo=3, rows=rows)
    assert int(computed) == n_held == int(((choice >= 3) & (choice < 7)).sum())
    assert out.dtype == jnp.float32
    _same(out, want)
    if draw == "no assignment held":  # zero trips
        assert n_held == 0 and not np.asarray(out).any()
    elif draw == "every assignment held":
        assert n_held == t * k
    else:
        assert rows >= 4096 or n_held > rows  # 8, 32: several trips; 4096: one block


@pytest.mark.parametrize("rows,where", [
    (1024, "one block"), (2048, "two blocks"), (512, "two trips")])
def test_a_token_with_several_held_assignments(rows, where):
    """Token 0 goes to the first and the last held expert: its two rows sit at
    the head of the sorted assignments and in the last expert's run — inside
    one block of 512 (300 held), or in two blocks of one trip of 2048, or in
    two trips of 512 (900 held)."""
    rng = np.random.default_rng(3)
    held = 300 if where == "one block" else 900
    t, k, d, f, n = 320, 3, 8, 8, 4
    choice = np.full((t, k), 20, np.int32)  # absent
    flat = 3 + rng.choice(t * k - 3, held - 2, replace=False)  # not token 0's slots
    choice.reshape(-1)[flat] = rng.integers(5, 5 + n, size=held - 2)
    choice[0] = [5, -1, 5 + n - 1]
    at = _positions(choice, 5, n)
    first, last = at[0, 0], at[0, 2]
    assert first == 0 and last >= held // 2
    _, width = combine_blocks(0, rows)
    assert width == 512
    if where == "one block":
        assert first // width == last // width
    elif where == "two blocks":
        assert first // rows == last // rows and first // width != last // width
    else:
        assert first // rows != last // rows
    u = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    gates = jnp.asarray(rng.random((t, k)), jnp.float32)
    w = _weights(rng, n, d, f)
    out, computed = held_expert_ffn(u, jnp.asarray(choice), gates, *w, lo=5, rows=rows)
    want, n_held = scatter_add_ffn(u, jnp.asarray(choice), gates, *w, lo=5, rows=rows)
    assert int(computed) == n_held == held
    _same(out, want)
    x = u[0]
    both = sum(g * ((jax.nn.silu(x @ w[0][e]) * (x @ w[1][e])) @ w[2][e])
               for g, e in ((gates[0, 0], 0), (gates[0, 2], n - 1)))
    np.testing.assert_allclose(out[0], both, rtol=1e-4, atol=1e-4)


def test_rows_that_differ_below_bfloat16_are_summed_in_float32():
    """bfloat16 tokens and weights. Token 0 goes to two experts whose rows
    ``c0 + c1 * 2**-8 + c2 * 2**-17`` and ``c0 + c1 * 2**-8 + c2 * 2**-18``
    agree in their leading 16 bits (and round to the same bfloat16), with
    gates +1 and -1: the float32 sum is ``c2 * 2**-18``; a combine that
    rounded ``y`` to bfloat16's 8 bits, or to 16, gives 0."""
    dt = jnp.bfloat16
    t, d, f = 8, 4, 3
    u = jnp.zeros((t, d), dt).at[:, 0].set(1.0)
    w_gate = jnp.zeros((2, d, f), dt).at[:, 0, :].set(3.0)
    w_up = jnp.zeros((2, d, f), dt).at[:, 0, :].set(jnp.array([1.5, 1.0, 1.75], dt))
    w_down = jnp.ones((2, f, d), dt).at[:, 1].set(2.0 ** -8)
    w_down = w_down.at[0, 2].set(2.0 ** -17).at[1, 2].set(2.0 ** -18)
    one = lambda e: np.asarray(held_expert_ffn(
        u, jnp.full((t, 1), e, jnp.int32), jnp.ones((t, 1), jnp.float32),
        w_gate, w_up, w_down, lo=0, rows=8)[0][0])
    y_a, y_b = one(0), one(1)
    to16 = lambda y: np.asarray(lax.reduce_precision(y, exponent_bits=8, mantissa_bits=15))
    assert (y_a != y_b).all() and (to16(y_a) == to16(y_b)).all()
    choice = jnp.full((t, 2), -1, jnp.int32).at[0].set(jnp.array([0, 1]))
    gates = jnp.zeros((t, 2), jnp.float32).at[0].set(jnp.array([1.0, -1.0]))
    out, computed = held_expert_ffn(u, choice, gates, w_gate, w_up, w_down, lo=0, rows=8)
    assert int(computed) == 2
    want, _ = scatter_add_ffn(u, choice, gates, w_gate, w_up, w_down, lo=0, rows=8)
    assert (np.asarray(want[0]) == y_a - y_b).all() and (y_a - y_b != 0).all()
    np.testing.assert_allclose(out[0], y_a - y_b, rtol=1e-6, atol=0)
    assert not np.asarray(out[1:]).any()


@pytest.mark.parametrize("rows,n_held", [
    (1024, 0), (1024, 1), (1024, 512), (1024, 513), (1024, 1024 + 513), (1024, 2500),
    (8, 20), (4096, 600)])
def test_the_count_is_the_positions_the_loop_visits(monkeypatch, rows, n_held):
    """``combined_positions`` (what ``sow_and_count`` reports) against a run
    of the loop in which every block the combine takes is counted."""
    visited = []
    real = grouped._add_rows

    def counted(out, tok, y):
        jax.debug.callback(lambda: visited.append(tok.shape[0]))
        return real(out, tok, y)
    monkeypatch.setattr(grouped, "_add_rows", counted)
    rng = np.random.default_rng(n_held)
    t, k, d, f, n = 700, 4, 8, 4, 3
    choice = np.full(t * k, 50, np.int32)
    choice[rng.choice(t * k, n_held, replace=False)] = rng.integers(2, 2 + n, size=n_held)
    u = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    out, computed = held_expert_ffn(
        u, jnp.asarray(choice.reshape(t, k)), jnp.ones((t, k), jnp.float32),
        *_weights(rng, n, d, f), lo=2, rows=rows)
    jax.block_until_ready(out)
    jax.effects_barrier()
    blocks, width = combine_blocks(n_held % rows, rows)
    assert int(computed) == n_held
    assert sum(visited) == int(combined_positions(jnp.int32(n_held), rows))
    assert set(visited) <= {width}
    # whole trips are walked whole; the last one up to its last held position
    assert sum(visited) == n_held // rows * rows + int(blocks) * width
    assert n_held <= sum(visited) < n_held + width


# -- the activation, and the combine where nothing is absent ------------------


def _fixed():
    """Dyadic inputs, two hidden units: every sum in the three products is exact or of two
    terms, so the bits do not depend on the order a backend adds in."""
    rng = np.random.default_rng(39)
    t, k, d, f, n = 12, 2, 4, 2, 3
    q = lambda *shape: jnp.asarray(rng.integers(-8, 9, size=shape) / 8.0, jnp.float32)
    u, gates = q(t, d), q(t, k)
    choice = jnp.asarray(rng.integers(-1, 5, size=(t, k)), jnp.int32)  # held [1, 4); -1, 0, 4 not
    return u, choice, gates, q(n, d, f), q(n, d, f), q(n, f, d)


# ``held_expert_ffn(*_fixed(), lo=1, rows=8)`` of the commit before ``activation`` and
# ``whole`` became arguments (PR 38's tree, jax 0.9.0, CPU), as the bytes of its float32 rows
_BEFORE = (
    "00000000000000000000000000000000a4bc563b9675aabd135dfcbcc142f03d4ed7d5bcade700bc0d34133c"
    "b61a85bde0a670be8c43973efd8109bd5bdc243ceccfc13cbe0e3fbe1a91443e0ebd953c468b713c468b71bc"
    "468b713c468bf1bb6acd013da20510bc7433ddbc6271073e00000000000000000000000000000000f48a843d"
    "0cb7b8bdb6b3443c2c4e8cbc39f704bc4eb7883d243781bd8ef840bc00000000000000000000000000000000"
    "551154ba071e853b528211bc7164833b")


def test_the_default_activation_gives_the_bits_it_gave():
    out, computed = held_expert_ffn(*_fixed(), lo=1, rows=8)
    assert int(computed) == 10 and np.asarray(out).tobytes().hex() == _BEFORE
    named, _ = held_expert_ffn(*_fixed(), lo=1, rows=8, activation=jax.nn.silu)
    assert np.array_equal(out, named)


def test_a_relu_gate_is_the_activation_it_is_given():
    u, choice, gates, w_gate, w_up, w_down = _fixed()
    out, computed = held_expert_ffn(u, choice, gates, w_gate, w_up, w_down, lo=1, rows=8,
                                    activation=jax.nn.relu)
    want = np.zeros(u.shape, np.float32)
    for t_, row in enumerate(np.asarray(choice)):
        for j, e in enumerate(row):
            if 1 <= e < 4:
                x = np.asarray(u[t_])
                h = np.maximum(x @ np.asarray(w_gate[e - 1]), 0.0) * (x @ np.asarray(w_up[e - 1]))
                want[t_] += float(gates[t_, j]) * (h @ np.asarray(w_down[e - 1]))
    assert int(computed) == 10
    np.testing.assert_allclose(out, want, rtol=1e-6, atol=1e-6)
    silu, _ = held_expert_ffn(u, choice, gates, w_gate, w_up, w_down, lo=1, rows=8)
    assert not np.allclose(out, silu, atol=1e-3)


@pytest.mark.parametrize("rows", [8, 32, 4096])
@pytest.mark.parametrize("pads", [0, 7])
def test_the_gather_equals_the_one_hot_combine_where_nothing_is_absent(rows, pads):
    """Every expert of the router held: each real token has its k rows among the held and
    ``whole`` sums them through the sort's inverse; the one-hot product over the same
    assignments gives the same float32 sums. Pads (-1) have no row in either."""
    rng = np.random.default_rng(rows + pads)
    t, k, d, f, n = 40, 3, 16, 8, 4
    u = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    choice = rng.integers(0, n, size=(t, k)).astype(np.int32)
    choice[:pads] = -1
    gates = jnp.asarray(rng.random((t, k)), jnp.float32)
    w = _weights(rng, n, d, f)
    args = (u, jnp.asarray(choice), gates, *w)
    gathered, computed = jax.jit(lambda *a: held_expert_ffn(*a, lo=0, rows=rows, whole=True))(*args)
    onehot, computed_1 = held_expert_ffn(*args, lo=0, rows=rows)
    assert int(computed) == int(computed_1) == (t - pads) * k
    assert gathered.dtype == jnp.float32 and not np.asarray(gathered[:pads]).any()
    _same(gathered, onehot)
    _same(gathered, scatter_add_ffn(*args, lo=0, rows=rows)[0])


# -- the whole branch's combine: the gather over the chunks that hold an assignment -----------

N_WHOLE = 4  # experts, all held
CHUNK = 8  # what the tests below set ``_GATHER_BLOCK`` to


def _left_padded(rng, t, k, pads):
    choice = rng.integers(0, N_WHOLE, size=(t, k)).astype(np.int32)
    choice[pads] = -1
    return choice


def _two_left_padded_rows(rng, t, k):
    """Two rows of ``t // 2`` positions, 3 and 1 whole leading chunks of pads."""
    pads = np.zeros(t, bool)
    pads[:3 * CHUNK], pads[t // 2:t // 2 + CHUNK] = True, True
    return _left_padded(rng, t, k, pads)


def _one_choice_missing(rng, t, k):
    """A real token with one of its choices -1 (what ``expert_skipped`` of
    ``benchmark/tools/prove_frozen_smallthinker.py`` hands this branch), beside a left pad."""
    choice = _left_padded(rng, t, k, np.arange(t) < 8)
    choice[11, 1] = -1
    choice[40:48, 0] = -1  # and a whole chunk of them
    return choice


# name -> (t, rows, choices)
WHOLE_CASES = {
    "two left-padded rows": (64, 32, _two_left_padded_rows),
    "pads scattered inside chunks": (64, 32, lambda rng, t, k: _left_padded(
        rng, t, k, rng.random(t) < 0.4)),
    "no pad": (64, 32, lambda rng, t, k: _left_padded(rng, t, k, np.zeros(t, bool))),
    "every token a pad": (64, 32, lambda rng, t, k: np.full((t, k), -1, np.int32)),
    "t not a multiple of the chunk": (44, 32, lambda rng, t, k: _left_padded(
        rng, t, k, np.arange(t) < 9)),
    "every token on one expert, several trips": (64, 16, lambda rng, t, k: np.where(
        np.arange(t)[:, None] < 16, -1, 2).astype(np.int32) * np.ones((1, k), np.int32)),
    "a real token with one choice -1": (64, 32, _one_choice_missing),
}


def _whole_case(monkeypatch, name):
    t, rows, draw = WHOLE_CASES[name]
    monkeypatch.setattr(grouped, "_GATHER_BLOCK", CHUNK)
    rng = np.random.default_rng(len(name))
    k, d, f = 3, 16, 8
    u = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    choice = jnp.asarray(draw(rng, t, k), jnp.int32)
    # a pad's gates are not zeroed here: what a token receives is decided by ``choice`` alone
    gates = jnp.asarray(0.25 + rng.random((t, k)), jnp.float32)
    return (u, choice, gates, *_weights(rng, N_WHOLE, d, f)), rows


def _nan_past_the_groups(real):
    """``grouped_matmul`` with every output row at or past the sum of its group sizes NaN:
    what the chip may hold where the kernel visited no tile or masked a row. No such row
    may reach a token, whichever chunks the combine visits."""
    def poisoned(x, w, sizes):
        y = real(x, w, sizes)
        return jnp.where((jnp.arange(x.shape[0]) < jnp.sum(sizes))[:, None], y, jnp.nan)
    return poisoned


@pytest.mark.parametrize("poison", [False, True], ids=["", "rows past the groups NaN"])
@pytest.mark.parametrize("name", list(WHOLE_CASES))
def test_the_whole_branch_equals_the_one_hot_combine_and_the_scatter_add(monkeypatch, name, poison):
    args, rows = _whole_case(monkeypatch, name)
    choice = np.asarray(args[1])
    want, n_held = scatter_add_ffn(*args, lo=0, rows=rows)
    onehot, computed_1 = held_expert_ffn(*args, lo=0, rows=rows)
    if poison:
        monkeypatch.setattr(grouped, "grouped_matmul", _nan_past_the_groups(grouped.grouped_matmul))
    out, computed = jax.jit(lambda *a: held_expert_ffn(*a, lo=0, rows=rows, whole=True))(*args)
    assert int(computed) == int(computed_1) == n_held == int((choice >= 0).sum())
    assert out.dtype == jnp.float32 and out.shape == want.shape
    assert np.isfinite(np.asarray(out)).all()
    assert not np.asarray(out)[(choice < 0).all(axis=1)].any()  # a pad reads zeros
    _same(out, onehot)
    _same(out, want)
    if name == "every token a pad":
        assert n_held == 0 and not np.asarray(out).any()
    elif name == "every token on one expert, several trips":
        assert n_held > 2 * rows


def test_a_choice_of_minus_one_on_a_real_token_leaves_out_that_row_alone(monkeypatch):
    args, rows = _whole_case(monkeypatch, "a real token with one choice -1")
    u, choice, gates, *w = args
    kept = jnp.where(choice < 0, 0, choice)  # the same tokens with the missing choices put back
    run = lambda c, g: np.asarray(held_expert_ffn(u, c, g, *w, lo=0, rows=rows, whole=True)[0])
    out = run(choice, gates)
    full = run(kept, jnp.where((choice < 0).all(axis=1, keepdims=True), 0.0, gates))
    gone = (np.asarray(choice) < 0) & ~(np.asarray(choice) < 0).all(axis=1, keepdims=True)
    assert gone[11, 1] and gone[40:48, 0].all() and gone.sum() == 9
    x = np.asarray(u)
    row = lambda t_, e: (np.asarray(jax.nn.silu(x[t_] @ w[0][e])) * (x[t_] @ np.asarray(w[1][e]))
                         ) @ np.asarray(w[2][e])
    for t_, j in zip(*np.nonzero(gone)):
        np.testing.assert_allclose(
            full[t_] - out[t_], float(gates[t_, j]) * row(t_, 0), rtol=1e-4, atol=1e-4)
    untouched = ~gone.any(axis=1)
    assert np.array_equal(out[untouched], full[untouched])


@pytest.mark.parametrize("name", list(WHOLE_CASES))
def test_gather_slots_counts_the_chunks_that_hold_an_assignment(monkeypatch, name):
    """``gather_slots`` (what the ``moe`` stats carry) against the chunks counted by hand and
    against a run of the branch in which every chunk the combine gathers for is counted."""
    args, rows = _whole_case(monkeypatch, name)
    choice = np.asarray(args[1])
    t, k = choice.shape
    width = t if t % CHUNK else CHUNK  # not whole chunks: one chunk
    live = (choice >= 0).reshape(t // width, width * k).any(axis=1)
    assert int(gather_slots(args[1])) == k * int(live.sum()) * width
    assert int((choice >= 0).sum()) <= int(gather_slots(args[1])) <= t * k
    if name == "two left-padded rows":
        assert live.tolist() == [False] * 3 + [True] + [False] + [True] * 3
    visited = []
    real_slice = lax.dynamic_slice_in_dim

    def counted(x, start, size, axis=0):
        # the slice of the sort's inverse, [t, k] int32, that a live chunk's gather takes
        if x.dtype == jnp.int32 and x.shape == choice.shape:
            jax.debug.callback(lambda: visited.append(size))
        return real_slice(x, start, size, axis)
    monkeypatch.setattr(lax, "dynamic_slice_in_dim", counted)
    out, _ = held_expert_ffn(*args, lo=0, rows=rows, whole=True)
    jax.block_until_ready(out)
    jax.effects_barrier()
    assert set(visited) <= {width} and k * sum(visited) == int(gather_slots(args[1]))


@pytest.mark.parametrize("dim,cap,tile", [
    (768, 1024, 768), (2048, 1024, 1024), (6144, 1024, 1024), (7680, 1024, 1024),
    (2560, 1024, 640), (2560, 512, 512), (1000, 512, 512)])
def test_tiles_are_chosen_by_shape(dim, cap, tile):
    """The two routed cells' widths keep the tile they had (1024 over 6144 / 2048 / 7680);
    a 2560-deep product is cut into four whole tiles, not two and a half."""
    assert grouped._tile(dim, cap) == tile
