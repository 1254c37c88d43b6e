"""Ring attention vs full attention parity on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepdfa_tpu.ops.ring_attention import (
    full_attention,
    ring_attention_sharded,
)
from deepdfa_tpu.parallel.mesh import local_mesh


def _qkv(b=2, s=32, h=4, h_kv=4, d=8, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h_kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h_kv, d)), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("sp", [2, 4])
def test_ring_matches_full(causal, sp):
    mesh = local_mesh(2 * sp, dp=2, sp=sp)
    q, k, v = _qkv()
    ref = full_attention(q, k, v, causal=causal)
    out = jax.jit(
        lambda q, k, v: ring_attention_sharded(q, k, v, mesh, causal=causal)
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.slow
def test_ring_matches_full_gqa():
    mesh = local_mesh(8, dp=2, sp=4)
    q, k, v = _qkv(h=8, h_kv=2)
    ref = full_attention(q, k, v, causal=True)
    out = ring_attention_sharded(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ring_with_padding_mask():
    """Left-padded batch (MSIVD contract: pad=eos on the left) — masked
    positions must not contribute, and masked queries must return 0 rows
    rather than NaN."""
    mesh = local_mesh(8, dp=2, sp=4)
    q, k, v = _qkv(s=16)
    kv_mask = np.ones((2, 16), dtype=bool)
    kv_mask[0, :5] = False
    kv_mask[1, :9] = False
    kv_mask = jnp.asarray(kv_mask)
    ref = full_attention(q, k, v, causal=True, kv_mask=kv_mask)
    out = ring_attention_sharded(q, k, v, mesh, causal=True, kv_mask=kv_mask)
    assert np.isfinite(np.asarray(out)).all()
    # compare only on unmasked query rows; fully-masked causal rows are
    # implementation-defined (we emit zeros)
    m = np.asarray(kv_mask)
    np.testing.assert_allclose(
        np.asarray(out)[m], np.asarray(ref)[m], atol=1e-5
    )


@pytest.mark.slow
def test_ring_bf16_inputs():
    mesh = local_mesh(4, dp=2, sp=2)
    q, k, v = _qkv()
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    ref = full_attention(q, k, v, causal=True)
    out = ring_attention_sharded(q, k, v, mesh, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
    )


def test_fully_masked_rows_emit_zeros():
    """Regression: with a finite _NEG_INF sentinel, a fully-masked query row
    used to get p=exp(0)=1 on every masked key (l>0), returning ~mean(V)
    instead of zeros — in both the ring recurrence and full_attention."""
    mesh = local_mesh(4, dp=2, sp=2)
    q, k, v = _qkv(s=16)
    kv_mask = np.ones((2, 16), dtype=bool)
    kv_mask[0, :] = False  # example 0: every position masked
    kv_mask = jnp.asarray(kv_mask)
    out_ring = ring_attention_sharded(q, k, v, mesh, causal=True, kv_mask=kv_mask)
    out_full = full_attention(q, k, v, causal=True, kv_mask=kv_mask)
    for out in (np.asarray(out_ring), np.asarray(out_full)):
        assert np.isfinite(out).all()
        np.testing.assert_allclose(out[0], 0.0)
        assert np.abs(out[1]).sum() > 0  # the live example is untouched


# -- the window ---------------------------------------------------------------


@pytest.mark.parametrize("window,block_q", [(1, 8), (5, 8), (8, 8), (13, 4), (20, 32)])
def test_a_window_is_the_band_of_a_masked_full_attention(window, block_q):
    """Blocks that read only ``[start - window + 1, end)`` against the whole scores under
    the band's mask, grouped-query heads and left padding included."""
    from deepdfa_tpu.ops.ring_attention import blocked_causal_attention, blocked_key_ranges

    q, k, v = _qkv(s=32, h=4, h_kv=2)
    kv_mask = jnp.asarray(np.arange(32)[None] >= np.array([[0], [9]]))
    got = blocked_causal_attention(q, k, v, kv_mask=kv_mask, block_q=block_q, window=window)
    t = np.arange(32)
    band = (t[None, :] <= t[:, None]) & (t[None, :] > t[:, None] - window)
    rep = lambda x: jnp.repeat(x, 2, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, rep(k)) * 8 ** -0.5
    ok = band[None, None] & np.asarray(kv_mask)[:, None, None, :]
    probs = jax.nn.softmax(jnp.where(ok, scores, -1e30), -1) * ok.any(-1, keepdims=True)
    want = jnp.einsum("bhqk,bkhd->bqhd", probs, rep(v))
    m = np.asarray(kv_mask)
    np.testing.assert_allclose(np.asarray(got)[m], np.asarray(want)[m], atol=1e-5)
    whole = full_attention(q, k, v, causal=True, kv_mask=kv_mask, window=window)
    np.testing.assert_allclose(np.asarray(whole)[m], np.asarray(want)[m], atol=1e-5)
    # no block reads a key its first query cannot see, nor the whole prefix past the window
    for start, end, lo in blocked_key_ranges(32, block_q, window):
        assert lo == max(0, start - window + 1) and end - lo <= window - 1 + block_q


@pytest.mark.parametrize("window", [32, 33, 4096])
def test_a_window_as_long_as_the_sequence_is_no_window(window):
    from deepdfa_tpu.ops.ring_attention import blocked_causal_attention, blocked_key_ranges

    q, k, v = _qkv(s=32, h=4, h_kv=1)
    kv_mask = jnp.asarray(np.arange(32)[None] >= np.array([[3], [0]]))
    none = blocked_causal_attention(q, k, v, kv_mask=kv_mask, block_q=8)
    got = blocked_causal_attention(q, k, v, kv_mask=kv_mask, block_q=8, window=window)
    assert np.array_equal(np.asarray(none), np.asarray(got))
    assert list(blocked_key_ranges(32, 8, window)) == list(blocked_key_ranges(32, 8))
    shorter = blocked_causal_attention(q, k, v, kv_mask=kv_mask, block_q=8, window=31)
    assert not np.array_equal(np.asarray(none)[:, -1], np.asarray(shorter)[:, -1])
