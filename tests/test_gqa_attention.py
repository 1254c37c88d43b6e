"""``ops/gqa_attention.py`` under the Pallas interpreter against
``full_attention``: global and windowed, grouped-query heads, left padding
and any other mask, the tiles it visits against the pairs that are needed,
the gradient (``blocked_causal_attention``'s), and the decoder taking the
kernel where it can run."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepdfa_tpu.llm import smallthinker
from deepdfa_tpu.ops import dispatch
from deepdfa_tpu.ops.gqa_attention import (
    default_tile,
    gqa_attention,
    supports,
    visited_pairs,
    visited_tiles,
)
from deepdfa_tpu.ops.latent_attention import first_tile
from deepdfa_tpu.ops.ring_attention import full_attention

D = 128


def _qkv(b=2, s=512, h=4, hk=2, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    make = lambda heads: jnp.asarray(rng.normal(size=(b, s, heads * D)), dtype)
    return make(h), make(hk), make(hk)


def _left_padded(b, s, pads):
    return jnp.asarray(np.arange(s)[None, :] >= np.asarray(pads)[:, None])


def _reference(q, k, v, mask, hk, window):
    b, s, _ = q.shape
    heads = lambda x: x.reshape(b, s, -1, D)
    with jax.default_matmul_precision("highest"):
        return full_attention(heads(q), heads(k), heads(v), causal=True, kv_mask=mask,
                              window=window).reshape(q.shape)


@pytest.mark.parametrize("window", [None, 1, 100, 128, 200, 511, 4096])
@pytest.mark.parametrize("block_q,block_k", [(128, 128), (256, 128), (128, 256)])
def test_the_kernel_equals_full_attention(window, block_q, block_k):
    q, k, v = _qkv()
    mask = _left_padded(2, 512, [0, 203])
    got = gqa_attention(q, k, v, mask, num_kv_heads=2, window=window, block_q=block_q,
                        block_k=block_k, interpret=True)
    want = _reference(q, k, v, mask, 2, window)
    m = np.asarray(mask)
    np.testing.assert_allclose(np.asarray(got)[m], np.asarray(want)[m], atol=2e-5)
    assert not np.asarray(got)[~m].any()  # a query with no key returns zeros


@pytest.mark.parametrize("layout", ["no_mask", "right_padded", "holes", "a_row_of_pads"])
def test_any_mask_is_computed_exactly(layout):
    q, k, v = _qkv(s=256, h=7, hk=1, seed=1)
    mask = {"no_mask": None,
            "right_padded": jnp.asarray(np.arange(256)[None] < np.array([[256], [130]])),
            "holes": jnp.asarray(np.random.default_rng(2).random((2, 256)) > 0.3),
            "a_row_of_pads": _left_padded(2, 256, [256, 17])}[layout]
    got = gqa_attention(q, k, v, mask, num_kv_heads=1, window=96, block_q=128, block_k=128,
                        interpret=True)
    want = _reference(q, k, v, mask, 1, 96)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_bfloat16_operands_stay_bfloat16():
    q, k, v = _qkv(s=256, dtype=jnp.bfloat16, seed=3)
    mask = _left_padded(2, 256, [40, 0])
    got = gqa_attention(q, k, v, mask, num_kv_heads=2, window=128, interpret=True)
    assert got.dtype == jnp.bfloat16
    want = _reference(*(x.astype(jnp.float32) for x in (q, k, v)), mask, 2, 128)
    m = np.asarray(mask)
    np.testing.assert_allclose(np.asarray(got, np.float32)[m], np.asarray(want)[m], atol=3e-2)


@pytest.mark.parametrize("window", [None, 100, 300])
@pytest.mark.parametrize("block_q,block_k", [(128, 128), (256, 128), (128, 256)])
def test_the_visited_tiles_hold_every_needed_pair_and_no_tile_without_one(window, block_q, block_k):
    s = 1024
    mask = _left_padded(3, s, [0, 300, 897])
    first = np.asarray(first_tile(mask, block_k))
    t = np.arange(s)
    for b_, pads in enumerate([0, 300, 897]):
        real = t >= pads
        ok = (t[None, :] <= t[:, None]) & real[None, :] & real[:, None]
        if window is not None:
            ok &= t[None, :] > t[:, None] - window
        for qi in range(s // block_q):
            band, inner, diag, hi = (int(x) for x in visited_tiles(qi, block_q, block_k, window))
            lo = max(int(first[b_]), band)
            rows = slice(qi * block_q, (qi + 1) * block_q)
            idle = 0
            for ki in range(s // block_k):
                has = ok[rows, ki * block_k:(ki + 1) * block_k].any()
                assert not has or lo <= ki < hi, (b_, qi, ki)  # every needed pair is visited
                idle += (lo <= ki < hi) and not has
                if max(lo, inner) <= ki < diag:  # no positional mask: every pair of a real key is in
                    cols = slice(ki * block_k, (ki + 1) * block_k)
                    causal_band = (t[None, cols] <= t[rows, None])
                    if window is not None:
                        causal_band &= t[None, cols] > t[rows, None] - window
                    assert causal_band.all()
            # a visited tile without a pair straddles the first real key: none with square tiles
            assert idle <= (block_q != block_k), (b_, qi, idle)
    pairs = float(visited_pairs(mask, block_q, block_k, window))
    assert pairs == sum(
        max(0, int(visited_tiles(qi, block_q, block_k, window)[3])
            - max(int(f), int(visited_tiles(qi, block_q, block_k, window)[0])))
        for f in first for qi in range(s // block_q)) * block_q * block_k
    needed = float(smallthinker.needed_pairs(mask, window))
    assert needed <= pairs < needed + 3 * (s // block_q) * 3 * block_q * max(block_q, block_k)


def test_gradients_are_blocked_causal_attentions():
    from deepdfa_tpu.ops.ring_attention import blocked_causal_attention

    q, k, v = _qkv(s=256, seed=4)
    mask = _left_padded(2, 256, [0, 60])
    heads = lambda x: x.reshape(2, 256, -1, D)
    loss = lambda f: lambda q, k, v: jnp.sum(jnp.where(mask[..., None], f(q, k, v), 0.0) ** 2)
    got = jax.grad(loss(lambda q, k, v: gqa_attention(
        q, k, v, mask, num_kv_heads=2, window=100, interpret=True)), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: blocked_causal_attention(
        heads(q), heads(k), heads(v), kv_mask=mask, window=100).reshape(q.shape)),
        argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-4)


def test_shapes_it_does_not_take():
    assert supports(8192, 28, 4, 128) and supports(16384, 28, 4, 128) and supports(128, 7, 1, 128)
    assert not supports(8192, 28, 4, 64) and not supports(200, 4, 2, 128)
    assert not supports(32768, 28, 4, 128) and not supports(256, 5, 2, 128)
    assert default_tile(8192) == 512 and default_tile(384) == 128
    q, k, v = _qkv(s=256)
    with pytest.raises(ValueError, match="do not tile"):
        gqa_attention(q, k, v, num_kv_heads=2, block_q=96, interpret=True)
    with pytest.raises(ValueError, match="takes no"):
        gqa_attention(q[:, :200], k[:, :200], v[:, :200], num_kv_heads=2, interpret=True)


# -- the decoder ---------------------------------------------------------------


@pytest.fixture(scope="module")
def decoder():
    """A decoder of heads of 128 (the kernel's width) and a window of 160 in
    rows of 256: two periods, so both kinds of layer twice."""
    cfg = smallthinker.tiny_smallthinker(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=128,
        sliding_window_size=160, vocab_size=64)
    model = smallthinker.SmallThinkerModel(cfg)
    ids = jnp.asarray(np.random.default_rng(0).integers(3, 64, (2, 256)), jnp.int32)
    mask = _left_padded(2, 256, [0, 131])
    params = nn.meta.unbox(model.init(jax.random.key(0), ids, mask)["params"])
    return cfg, model, params, ids, mask


@pytest.mark.parametrize("kernel,fused", [(True, 8), (None, 0)])
def test_the_decoder_takes_the_kernel_where_it_can_run(decoder, monkeypatch, kernel, fused):
    cfg, model, params, ids, mask = decoder
    monkeypatch.setattr(dispatch, "device_mode", lambda: None)
    want, _ = model.apply({"params": params}, ids, mask, mutable=["stats"])
    monkeypatch.setattr(dispatch, "device_mode", lambda: kernel)
    got, sown = model.apply({"params": params}, ids, mask, mutable=["stats"])
    m = np.asarray(mask)
    np.testing.assert_allclose(np.asarray(got)[m], np.asarray(want)[m], atol=2e-4)
    attn = jax.device_get(sown["stats"]["attn"])
    assert attn["layers"] == 8 and attn["window_layers"] == 6 and attn["fused"] == fused
    needed = 2 * float(smallthinker.needed_pairs(mask, None)) + 6 * float(
        smallthinker.needed_pairs(mask, 160))
    assert attn["pairs_needed"] == needed <= attn["pairs_computed"]
    if kernel:  # tiles of 256 (one a row): the row of 125 real tokens is one tile, as the other
        assert attn["pairs_computed"] == 8 * 2 * 256 * 256
    else:  # blocks of 16 queries over their keys, pads and all
        from deepdfa_tpu.ops.ring_attention import blocked_key_ranges

        blocks = lambda w: 2 * sum((e - a) * (e - lo) for a, e, lo in blocked_key_ranges(256, 16, w))
        assert attn["pairs_computed"] == 2 * blocks(None) + 6 * blocks(160)
