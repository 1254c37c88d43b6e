"""Degree-2 power retention (``ops/power_retention.py``, and the Pallas kernel
``ops/power_retention_kernel.py`` under the interpreter) against a float64
position-by-position recurrence: the feature map's identity (``phi``'s and
the kernel's diagonal tiles'), every left-pad layout (leading chunks wholly of
pads skipped, a row of pads alone), results that do not hang on the chunk, the
grouped-query mapping, the visited-chunk counts, and the op raising where it is
differentiated."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepdfa_tpu.ops.power_retention import (
    EPS,
    chunks_computed,
    chunks_needed,
    phi,
    power_retention,
    power_retention_plain,
    supports,
)

# pads before each row's first real token, at s = 128 (chunks of 32 / 64 / 128)
LAYOUTS = {"none": 0, "inside_a_chunk": 5, "a_whole_chunk": 32, "across_chunks": 70,
           "all_but_one": 127, "all_pads": 128}


def recurrence(q, k, v, log_g, mask, eps=EPS):
    """The state form one position at a time, float64, with the full tensor
    square ``k (x) k`` (no symmetric map): ``S_t = g_t S_{t-1} + (k (x) k) v^T``
    at real tokens, ``o_t = (q (x) q / d) S_t / ((q (x) q / d) z_t + eps)``."""
    q, k, v, log_g = (np.asarray(x, np.float64) for x in (q, k, v, log_g))
    b, s, h, d = q.shape
    hk = k.shape[2]
    rep = h // hk
    out = np.zeros((b, s, h, d))
    for bi in range(b):
        for g in range(hk):
            S, z = np.zeros((d * d, d)), np.zeros(d * d)
            for t in range(s):
                if mask[bi, t]:
                    kk = np.outer(k[bi, t, g], k[bi, t, g]).ravel()
                    gt = math.exp(log_g[bi, t, g])
                    S, z = gt * S + np.outer(kk, v[bi, t, g]), gt * z + kk
                for r in range(rep):
                    qq = np.outer(q[bi, t, g * rep + r], q[bi, t, g * rep + r]).ravel() / d
                    out[bi, t, g * rep + r] = qq @ S / (qq @ z + eps)
    return out


def _inputs(b, s, h, hk, d, pads, seed=0):
    """q, k, v bfloat16-representable (the kernel's operands are the
    decoder's bfloat16; what it adds is its own rounding), log-gates, mask."""
    rng = np.random.default_rng(seed)
    normal = lambda *shape: np.asarray(
        jnp.asarray(rng.standard_normal(shape), jnp.bfloat16), np.float32)
    q, k, v = normal(b, s, h, d), normal(b, s, hk, d), normal(b, s, hk, d)
    log_g = -rng.uniform(0.0, 0.08, (b, s, hk)).astype(np.float32)
    mask = np.arange(s)[None, :] >= np.asarray(pads)[:, None]
    return q, k, v, log_g, mask


def _flat(x):
    return jnp.asarray(x.reshape(*x.shape[:2], -1), jnp.bfloat16)


def _rel(got, want, mask):
    got, want = np.asarray(got, np.float64)[mask], np.asarray(want, np.float64)[mask]
    return np.abs(got - want).max() / np.abs(want).max() if want.size else 0.0


@pytest.mark.parametrize("d", [3, 16, 128])
def test_phi_is_the_square_of_the_dot_product(d):
    rng = np.random.default_rng(d)
    q, k = rng.standard_normal((2, 5, d)).astype(np.float32)
    got = np.einsum("nD,nD->n", np.asarray(phi(q), np.float64), np.asarray(phi(k), np.float64))
    want = np.einsum("nd,nd->n", q, k, dtype=np.float64) ** 2
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * want.max())
    assert phi(q).shape[-1] == d * (d + 1) // 2


def _diagonal_tiles(x, traced):
    """The kernel's 65 tiles of phi of the rows of ``x`` [M, 128] float32, by
    its own helper under the Pallas interpreter: [M, 65 x 128]. ``traced``
    takes the tile's index from a loop, as the kernel does."""
    from jax.experimental import pallas as pl

    from deepdfa_tpu.ops.power_retention_kernel import LANES, TILES, _phi_tile

    def kernel(x_ref, o_ref):
        if traced:
            def body(a, carry):
                o_ref[:, pl.ds(pl.multiple_of(a * LANES, LANES), LANES)] = _phi_tile(x_ref[...], a)
                return carry

            jax.lax.fori_loop(0, TILES, body, 0)
        else:
            for a in range(TILES):
                o_ref[:, a * LANES:(a + 1) * LANES] = _phi_tile(x_ref[...], a)

    out = jax.ShapeDtypeStruct((x.shape[0], TILES * LANES), jnp.float32)
    return np.asarray(pl.pallas_call(kernel, out_shape=out, interpret=True)(jnp.asarray(x)))


@pytest.mark.parametrize("traced", [False, True], ids=["static_tile", "traced_tile"])
def test_the_kernels_diagonal_tiles_are_phi(traced):
    """Tile ``a`` is ``x * roll(x, a)``: with the key side's factors (1, 2, ..,
    2, 1 over d) the tiles' dot product is ``(q . k)^2 / d``, and they hold
    phi's products — tile 64's 64 pairs twice, every other once."""
    from deepdfa_tpu.ops.power_retention_kernel import LANES, TILES, _tile_coef

    rng = np.random.default_rng(128)
    q, k = rng.standard_normal((2, 8, LANES)).astype(np.float32)
    tq, tk = _diagonal_tiles(q, traced), _diagonal_tiles(k, traced)
    coef = np.repeat([float(_tile_coef(a)) for a in range(TILES)], LANES)
    got = np.einsum("nD,nD->n", tq.astype(np.float64), tk * coef)
    want = np.einsum("nd,nd->n", q, k, dtype=np.float64) ** 2 / LANES
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * want.max())
    a, b = np.triu_indices(LANES)
    for x, tiles in ((q, tq), (k, tk)):
        last = tiles[:, -LANES:]
        np.testing.assert_array_equal(last[:, :LANES // 2], last[:, LANES // 2:])
        np.testing.assert_array_equal(np.sort(tiles[:, :-(LANES // 2)], axis=1),
                                      np.sort(x[:, a] * x[:, b], axis=1))


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_plain_form_against_the_float64_recurrence(layout):
    pads = [LAYOUTS[layout], 0]
    q, k, v, log_g, mask = _inputs(2, 128, 4, 2, 8, pads)
    want = recurrence(q, k, v, log_g, mask)
    got = power_retention_plain(q, k, v, log_g, jnp.asarray(mask), chunk=32)
    assert np.isfinite(np.asarray(got)).all()
    assert _rel(got, want, mask) < 1e-5


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_kernel_against_the_float64_recurrence(layout):
    """At the kernel's head width (128); bfloat16 operands on the MXU, so the
    tolerance is bfloat16's (``o`` is handed out in bfloat16), not float32's."""
    pads = [LAYOUTS[layout], 37]
    q, k, v, log_g, mask = _inputs(2, 128, 4, 2, 128, pads)
    want = recurrence(q, k, v, log_g, mask)
    got = power_retention(_flat(q), _flat(k), _flat(v), jnp.asarray(log_g), jnp.asarray(mask),
                          interpret=True, chunk=32)
    got = np.asarray(got, np.float64).reshape(q.shape)
    assert np.isfinite(got).all()
    assert _rel(got, want, mask) < 1e-2
    # a chunk wholly of leading pads is never visited: its output is zeros
    for row, p in enumerate(pads):
        assert not got[row, :p // 32 * 32].any()


@pytest.mark.parametrize("chunk", [16, 32, 64, 128])
def test_plain_form_does_not_hang_on_the_chunk(chunk):
    q, k, v, log_g, mask = _inputs(2, 128, 4, 2, 8, [45, 3], seed=1)
    base = power_retention_plain(q, k, v, log_g, jnp.asarray(mask), chunk=8)
    got = power_retention_plain(q, k, v, log_g, jnp.asarray(mask), chunk=chunk)
    assert _rel(got, base, mask) < 1e-5


@pytest.mark.parametrize("chunk", [32, 64, 128])
def test_kernel_does_not_hang_on_the_chunk(chunk):
    q, k, v, log_g, mask = _inputs(1, 128, 2, 1, 128, [45], seed=2)
    plain = power_retention_plain(q, k, v, log_g, jnp.asarray(mask), chunk=16)
    got = power_retention(_flat(q), _flat(k), _flat(v), jnp.asarray(log_g), jnp.asarray(mask),
                          interpret=True, chunk=chunk)
    assert _rel(np.asarray(got).reshape(q.shape), plain, mask) < 1e-2


def test_query_heads_read_their_groups_key_value_head():
    """Query head ``i`` reads key/value head ``i // (h / hk)``: changing the
    second key/value head moves the second group's query heads alone."""
    q, k, v, log_g, mask = _inputs(1, 64, 6, 3, 8, [0], seed=3)
    base = np.asarray(power_retention_plain(q, k, v, log_g, jnp.asarray(mask), chunk=16))
    v2 = v.copy()
    v2[:, :, 1] += 1.0
    moved = np.asarray(power_retention_plain(q, k, v2, log_g, jnp.asarray(mask), chunk=16))
    changed = np.abs(moved - base).max(axis=(0, 1, 3)) > 1e-3
    assert changed.tolist() == [False, False, True, True, False, False]


def test_the_kernels_groups_are_the_plain_forms():
    q, k, v, log_g, mask = _inputs(1, 64, 6, 3, 128, [10], seed=4)
    plain = power_retention_plain(q, k, v, log_g, jnp.asarray(mask), chunk=16)
    got = power_retention(_flat(q), _flat(k), _flat(v), jnp.asarray(log_g), jnp.asarray(mask),
                          interpret=True, chunk=32)
    assert _rel(np.asarray(got).reshape(q.shape), plain, mask) < 1e-2


def test_a_left_padded_row_reads_what_the_row_alone_would():
    q, k, v, log_g, _ = _inputs(1, 64, 4, 2, 8, [0], seed=5)
    alone = power_retention_plain(q[:, 40:], k[:, 40:], v[:, 40:], log_g[:, 40:], None, chunk=8)
    mask = jnp.asarray(np.arange(64)[None] >= 40)
    padded = power_retention_plain(q, k, v, log_g, mask, chunk=16)
    np.testing.assert_allclose(np.asarray(padded)[:, 40:], np.asarray(alone), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("interpret", [None, True])
def test_the_op_raises_where_it_is_differentiated(interpret):
    q, k, v, log_g, mask = _inputs(1, 64, 2, 1, 128, [0], seed=6)
    loss = lambda qq: jnp.sum(power_retention(qq, _flat(k), _flat(v), jnp.asarray(log_g),
                                              jnp.asarray(mask), interpret=interpret, chunk=32))
    with pytest.raises(NotImplementedError, match="no backward"):
        jax.grad(loss)(_flat(q))


def test_visited_and_needed_chunks_by_hand():
    mask = jnp.asarray(np.arange(128)[None] >= np.array([0, 5, 32, 70, 128])[:, None])
    assert int(chunks_needed(mask, 32)) == 4 + 4 + 3 + 2 + 0
    assert int(chunks_computed(mask, 32, fused=True)) == 4 + 4 + 3 + 2 + 0
    assert int(chunks_computed(mask, 32, fused=False)) == 5 * 4
    scattered = jnp.asarray(np.array([[False, True] * 64]))  # the kernel visits from the first real one
    assert int(chunks_computed(scattered, 32, fused=True)) == 4


def test_what_the_kernel_takes():
    assert supports(8192, 40, 8, 128, 128) and supports(8192, 40, 8, 128, 64)
    assert not supports(8192, 40, 8, 64, 128) and not supports(8192, 40, 6, 128, 128)
    assert not supports(8200, 40, 8, 128, 128) and not supports(8192, 40, 8, 128, 4)
    with pytest.raises(ValueError, match="takes no"):
        power_retention(jnp.zeros((1, 96, 256)), jnp.zeros((1, 96, 128)), jnp.zeros((1, 96, 128)),
                        jnp.zeros((1, 96, 1)), chunk=64, interpret=True)
