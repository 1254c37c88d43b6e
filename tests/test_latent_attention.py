"""``ops/latent_attention`` (the Pallas interpreter standing in for the chip)
against ``full_attention`` at the published widths (query/key 192 = 128 + 64
shared, value 128); which tiles it visits; its gradient; when
``LatentAttention`` takes it, and the counter that says so on ``loss.sync``;
and the CodeBERT step, which shares none of it, unchanged."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepdfa_tpu.llm.longcat import LongcatModel, tiny_longcat
from deepdfa_tpu.ops import dispatch
from deepdfa_tpu.ops import latent_attention as la
from deepdfa_tpu.ops.ring_attention import blocked_causal_attention, full_attention

HEADS, NOPE, ROPE, V = 2, 128, 64, 128
# real tokens a row (left-padded): all real, a boundary inside a tile, a
# boundary on a tile's edge, all pad
REAL = {256: (256, 91, 128, 0), 384: (384, 200, 256, 0)}


def _operands(s, dtype=jnp.float32, b=4, seed=0):
    ks = jax.random.split(jax.random.key(seed + s), 4)
    q_nope = jax.random.normal(ks[0], (b, s, HEADS * NOPE), dtype)
    q_rope = jax.random.normal(ks[1], (b, s, HEADS * ROPE), dtype)
    k_rope = jax.random.normal(ks[2], (b, s, ROPE), dtype)
    kv = jax.random.normal(ks[3], (b, s, HEADS * (NOPE + V)), dtype)
    return q_nope, q_rope, k_rope, kv


def _left_padded(s, real=None):
    real = np.asarray(REAL[s] if real is None else real)
    return np.arange(s)[None, :] >= (s - real)[:, None]


def _written_out(q_nope, q_rope, k_rope, kv, mask, attention=full_attention, **kw):
    """Heads apart, the shared keys broadcast to every head and concatenated,
    as ``LatentAttention`` builds them for the XLA path."""
    b, s, _ = q_nope.shape
    q_nope, q_rope, kv = (x.reshape(b, s, HEADS, -1) for x in (q_nope, q_rope, kv))
    k = jnp.concatenate(
        [kv[..., :NOPE], jnp.broadcast_to(k_rope[:, :, None], (b, s, HEADS, ROPE))], axis=-1)
    return attention(jnp.concatenate([q_nope, q_rope], -1), k, kv[..., NOPE:],
                     kv_mask=mask, **kw).reshape(b, s, HEADS * V)


@pytest.mark.parametrize("s,block_q,block_k", [
    (256, 128, 128), (256, 256, 128), (256, 128, 256), (256, 64, 128), (256, 256, 256),
    (384, 128, 128), (384, 64, 128), (384, 384, 128), (384, 128, 384)])
def test_the_kernel_equals_full_attention(s, block_q, block_k):
    ops = _operands(s)
    mask = _left_padded(s)
    got = la.latent_attention(*ops, jnp.asarray(mask), num_heads=HEADS, block_q=block_q,
                              block_k=block_k, interpret=True)
    want = _written_out(*ops, jnp.asarray(mask), causal=True)
    assert got.shape == (4, s, HEADS * V) and got.dtype == want.dtype
    np.testing.assert_allclose(got, want, atol=2e-5)
    # a query with no key to attend (every left pad) returns zeros
    assert not np.asarray(got)[~mask].any() and np.asarray(got)[mask].all()


@pytest.mark.parametrize("layout", ["no_mask", "right_padded", "holes"])
def test_any_mask_is_computed_exactly(layout):
    """Only left padding is skipped; every mask is honoured in the tile."""
    s = 256
    ops = _operands(s, b=2, seed=1)
    mask = {"no_mask": None,
            "right_padded": np.arange(s)[None, :] < np.array([[s], [70]]),
            "holes": np.random.default_rng(0).random((2, s)) < 0.5}[layout]
    mask = None if mask is None else jnp.asarray(mask)
    got = la.latent_attention(*ops, mask, num_heads=HEADS, block_q=128, block_k=128,
                              interpret=True)
    np.testing.assert_allclose(got, _written_out(*ops, mask, causal=True), atol=2e-5)


def test_bfloat16_operands_stay_bfloat16():
    """The cell's dtype: products of bfloat16 operands into float32, the
    probabilities cast for the product with ``v``, ``o`` in bfloat16."""
    s = 256
    ops = _operands(s, jnp.bfloat16)
    mask = jnp.asarray(_left_padded(s))
    got = la.latent_attention(*ops, mask, num_heads=HEADS, interpret=True)
    assert got.dtype == jnp.bfloat16
    want = _written_out(*(x.astype(jnp.float32) for x in ops), mask, causal=True)
    err = jnp.abs(got.astype(jnp.float32) - want)
    assert float(err.max()) <= 2e-2 * float(jnp.abs(want).max())


@pytest.mark.parametrize("block_q,block_k", [(128, 128), (512, 512), (256, 512), (512, 128)])
def test_the_visited_tiles_hold_every_real_pair(block_q, block_k):
    """Counted against a numpy mask: a tile is visited whenever it holds a
    real key at or before a query of the tile; with square tiles and left
    padding, only then."""
    s = 2048
    real = np.array([2048, 700, 1, 513, 1536, 0, 1025, 300])
    mask = _left_padded(s, real)
    first = np.asarray(la.first_tile(jnp.asarray(mask), block_k))
    pair = mask[:, None, :] & (np.arange(s)[None, :] <= np.arange(s)[:, None])  # [b, q, k]
    needed = pair.reshape(len(real), s // block_q, block_q, s // block_k, block_k).any((2, 4))
    visited = np.zeros_like(needed)
    for b in range(len(real)):
        for qi in range(s // block_q):
            diag, hi = la.visited_tiles(qi, block_q, block_k)
            visited[b, qi, first[b]:hi] = True
            # tiles before ``diag`` lie wholly under the diagonal: no causal mask there
            assert (diag * block_k <= qi * block_q) and ((diag + 1) * block_k > qi * block_q)
    assert not (needed & ~visited).any()
    # a row all real visits its causal half and no more; a row all pad, nothing
    assert np.array_equal(visited[0], needed[0]) and not visited[5].any()
    if block_q == block_k:
        assert np.array_equal(needed, visited)
        # what the skipping saves at the cell's padding
        assert visited.sum() < 0.45 * (s // block_q) * (s // block_q + 1) / 2 * len(real)


def test_gradients_are_blocked_causal_attentions():
    s = 256
    ops = _operands(s, seed=2)
    mask = jnp.asarray(_left_padded(s))
    w = jax.random.normal(jax.random.key(9), (4, s, HEADS * V))
    through = lambda f: jax.grad(lambda *xs: jnp.sum(f(*xs) * w), argnums=(0, 1, 2, 3))(*ops)
    got = through(lambda *xs: la.latent_attention(*xs, mask, num_heads=HEADS, block_q=128,
                                                  block_k=128, interpret=True))
    want = through(lambda *xs: _written_out(*xs, mask, attention=blocked_causal_attention))
    for g, e in zip(got, want):
        assert g.shape == e.shape and float(jnp.abs(e).max()) > 0
        np.testing.assert_allclose(g, e, atol=1e-6)


def test_shapes_it_does_not_take():
    assert la.supports(2048, 64, 128, 64, 128)
    cfg = tiny_longcat()
    assert not la.supports(128, cfg.num_attention_heads, cfg.qk_nope_head_dim,
                           cfg.qk_rope_head_dim, cfg.v_head_dim)
    assert not la.supports(2048 + 64, 64, 128, 64, 128)  # no whole tiles
    assert not la.supports(2048, 63, 128, 64, 128)  # heads go in pairs
    with pytest.raises(ValueError, match="takes no"):
        la.latent_attention(*_operands(192), num_heads=HEADS, interpret=True)
    with pytest.raises(ValueError, match="do not tile"):
        la.latent_attention(*_operands(256), num_heads=HEADS, block_q=96, interpret=True)


# -- behind LatentAttention ---------------------------------------------------


@pytest.fixture(scope="module")
def decoder():
    """``tiny_longcat`` with the attention widths the kernel takes."""
    cfg = tiny_longcat(num_attention_heads=HEADS, qk_nope_head_dim=NOPE,
                       qk_rope_head_dim=ROPE, v_head_dim=V)
    model = LongcatModel(cfg)
    ids = jax.random.randint(jax.random.key(3), (3, 128), 3, cfg.vocab_size)
    mask = jnp.asarray(_left_padded(128, (128, 50, 1)))
    params = model.init(jax.random.key(0), ids, mask)["params"]
    return cfg, model, params, ids, mask


@pytest.mark.parametrize("kernel,fused", [(True, 4), (None, 0)])
def test_the_decoder_takes_the_kernel_where_it_can_run(decoder, monkeypatch, kernel, fused):
    cfg, model, params, ids, mask = decoder
    plain = model.apply({"params": params}, ids, mask)  # the CPU: no kernel
    monkeypatch.setattr(dispatch, "device_mode", lambda: kernel)
    apply = lambda p, i, m: model.apply({"params": p}, i, m, mutable=["stats"])
    hidden, sown = apply(params, ids, mask)
    assert {k: int(v) for k, v in sown["stats"]["attn"].items()} == {"layers": 4, "fused": fused}
    assert ("latent_attention_fwd" in str(jax.make_jaxpr(apply)(params, ids, mask))) == bool(fused)
    real = np.asarray(mask)
    np.testing.assert_allclose(np.asarray(hidden)[real], np.asarray(plain)[real], atol=2e-4)
    # the routing counts ride beside, untouched
    assert int(sown["stats"]["moe"]["dropped"]) == 0 and int(sown["stats"]["moe"]["layers"]) == 2


def test_tiny_longcats_own_shapes_keep_the_blocked_path(monkeypatch):
    monkeypatch.setattr(dispatch, "device_mode", lambda: True)
    cfg = tiny_longcat()
    model = LongcatModel(cfg)
    ids = jnp.zeros((1, 128), jnp.int32)
    _, sown = model.apply(model.init(jax.random.key(0), ids), ids, mutable=["stats"])
    assert {k: int(v) for k, v in sown["stats"]["attn"].items()} == {"layers": 4, "fused": 0}


@pytest.mark.parametrize("kernel,fused", [(True, 4), (None, 0)])
def test_the_step_says_on_loss_sync_which_attention_it_ran(decoder, monkeypatch, kernel, fused):
    """Through ``JointTrainer.train`` with the decoder frozen: ``attn_layers``
    is two blocks a layer, ``attn_fused`` all of them or none, beside the
    routing counts on the same span."""
    from deepdfa_tpu.llm.dataset import HashTokenizer, encode_functions
    from deepdfa_tpu.llm.fusion import FusionModel
    from deepdfa_tpu.llm.joint import JointConfig, JointTrainer
    from deepdfa_tpu.obs import Tracer, TrainTelemetry

    monkeypatch.setattr(dispatch, "device_mode", lambda: kernel)
    cfg, model, params, _, _ = decoder
    jcfg = JointConfig(block_size=128, train_batch_size=2, eval_batch_size=2, epochs=1,
                       train_llm=False, use_gnn=False, first_eval_steps=100)
    funcs = [f"int f{i}(int a) {{ return a + {i}; }}" * (1 + i % 3) for i in range(4)]
    examples = encode_functions(funcs, [0, 1, 0, 1], HashTokenizer(vocab_size=cfg.vocab_size),
                                jcfg.block_size, indices=list(range(4)))
    fusion = FusionModel(gnn_cfg=None, input_dim=8, llm_hidden_size=cfg.hidden_size,
                         use_gnn=False, pool="last")
    trainer = JointTrainer(llm=model, llm_params=params, fusion=fusion, cfg=jcfg, join=None)
    trainer.telemetry = TrainTelemetry(tracer=Tracer(proc="train", max_spans=256))
    trainer.train(examples, examples)
    syncs = [s for s in trainer.telemetry.tracer.spans() if s.name == "loss.sync"]
    assert len(syncs) == 2
    for span in syncs:
        assert span.attrs["attn_layers"] == 2 * cfg.num_layers == 4
        assert span.attrs["attn_fused"] == fused and span.attrs["moe_dropped"] == 0
    assert all(np.isfinite(e["train_loss"]) for e in trainer.history if "train_loss" in e)


# -- what shares none of it ---------------------------------------------------

# sha256 of ``jit(train_step).lower(...).as_text()`` on the commit before this
# kernel (PR 31, jax 0.9.0), made by this very function there. A PR that means
# to change the CodeBERT step replaces them; one that does not must not.
# With the GGNN, moved on purpose by PR 38 (from 79460ed0...): the step hands
# out the counts of the GGNN's view of the graph budget (this budget, 512
# nodes, is under the size that gets views: the GGNN itself runs as it did).
# Without the GGNN the step is still PR 31's.
CODEBERT_STEPS = {
    False: "94d3cfb911c01bfc9929d4e361f729bda8844c18e772a057805410f8d36eb517",
    True: "696b9d8ee21ed10c5ab96b693d469221ce16c26c2772a3196b8005f7b6d89468",
}


def _lowered_codebert_step(use_gnn: bool) -> str:
    from deepdfa_tpu.config import GGNNConfig
    from deepdfa_tpu.data.synthetic import random_dataset
    from deepdfa_tpu.llm.dataset import GraphJoin, HashTokenizer, encode_functions, text_batches
    from deepdfa_tpu.llm.fusion import FusionModel
    from deepdfa_tpu.llm.joint import JointConfig, JointTrainer
    from deepdfa_tpu.llm.roberta import RobertaEncoder, tiny_roberta

    cfg = tiny_roberta(vocab_size=256)
    enc = RobertaEncoder(cfg)
    jcfg = JointConfig(block_size=32, train_batch_size=4, eval_batch_size=4, epochs=1,
                       train_llm=True, use_gnn=use_gnn)
    graphs = random_dataset(12, seed=0, input_dim=8)
    funcs = [f"int f{i}(int a) {{ return a + {i}; }}" * (1 + i % 3) for i in range(12)]
    examples = encode_functions(
        funcs, [i % 2 for i in range(12)], HashTokenizer(vocab_size=cfg.vocab_size),
        jcfg.block_size, indices=[g.gid for g in graphs])
    fusion = FusionModel(
        gnn_cfg=GGNNConfig(hidden_dim=8, n_steps=2) if use_gnn else None, input_dim=8,
        llm_hidden_size=cfg.hidden_size, use_gnn=use_gnn, pool="cls")
    ids = jnp.zeros((2, jcfg.block_size), jnp.int32)
    params = enc.init(jax.random.key(0), ids, jnp.ones(ids.shape, bool))["params"]
    join = GraphJoin.from_list(graphs, max_nodes=512, max_edges=1024) if use_gnn else None
    trainer = JointTrainer(llm=enc, llm_params=params, fusion=fusion, cfg=jcfg, join=join)
    batch = trainer._joined(next(text_batches(examples, jcfg.train_batch_size)))
    state = trainer._build(3, batch)
    launch = trainer._steps[0]
    jitted = launch.__closure__[launch.__code__.co_freevars.index("jitted_train_step")]
    return jitted.cell_contents.lower(state, None, batch).as_text()


@pytest.mark.parametrize("use_gnn", [False, True])
def test_the_codebert_step_is_lowered_as_before(use_gnn):
    """``RobertaEncoder`` and ``ops/flash_attention`` share no line of the
    latent-attention kernel: the step is the parent's byte for byte."""
    text = _lowered_codebert_step(use_gnn)
    assert hashlib.sha256(text.encode()).hexdigest() == CODEBERT_STEPS[use_gnn]
