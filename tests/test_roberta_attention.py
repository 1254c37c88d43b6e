"""``_SelfAttention`` through ``ops/flash_attention`` (the Pallas interpreter
standing in for the chip) against the einsum-softmax path it replaces on a
TPU, when each of the two is taken, and the counter that says which ran."""


import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepdfa_tpu.llm.roberta import RobertaEncoder, _SelfAttention, tiny_roberta
from deepdfa_tpu.ops import dispatch
from deepdfa_tpu.ops import flash_attention as flash

HEADS, HEAD_DIM = 4, 64


def _cfg(**kw):
    kw.setdefault("attention_probs_dropout_prob", 0.0)
    kw.setdefault("hidden_dropout_prob", 0.0)
    return tiny_roberta(hidden_size=HEADS * HEAD_DIM, num_attention_heads=HEADS,
                        intermediate_size=512, **kw)


@pytest.fixture
def interpreted(monkeypatch):
    """The kernels are there, as on a one-chip TPU, under the interpreter."""
    monkeypatch.setattr(dispatch, "device_mode", lambda: True)


def _pad_mask(layout: str, s: int) -> np.ndarray | None:
    """[3, s] bool, True = real token; the framework pads on the left."""
    real = {"none": None, "left_mixed": (s, s - 37, s // 2 + 3), "one_row_8_real": (s, 8, s - 1)}[layout]
    if real is None:
        return None
    return np.arange(s)[None, :] >= (s - np.asarray(real))[:, None]


@pytest.mark.parametrize("layout", ["none", "left_mixed", "one_row_8_real"])
@pytest.mark.parametrize("s", [128, 256])
def test_fused_attention_matches_the_einsum_softmax_path(s, layout):
    """Outputs and the gradients of the q/k/v projections, on the rows of real
    tokens (a pad query sees pad keys in the kernel and real keys in the XLA
    path; nothing reads either). The CPU's XLA path multiplies in float32 and
    the kernel rounds each product's operands to bfloat16, as the TPU's XLA
    path does: the tolerance is bfloat16's."""
    cfg = _cfg()
    attn = _SelfAttention(cfg)
    kx, kw, kp = jax.random.split(jax.random.key(s), 3)
    x = jax.random.normal(kx, (3, s, cfg.hidden_size), jnp.float32)
    w = jax.random.normal(kw, (3, s, cfg.hidden_size), jnp.float32)
    mask = _pad_mask(layout, s)
    pad = None if mask is None else jnp.asarray(mask)
    real = jnp.ones((3, s, 1)) if mask is None else jnp.asarray(mask)[:, :, None]
    params = nn.meta.unbox(attn.init(kp, x, pad)["params"])
    # biases away from zero, so that their gradients are compared too
    params = jax.tree.map(
        lambda p: p + 0.1 * jax.random.normal(kp, p.shape) if p.ndim == 1 else p, params)

    def run(fused):
        def loss(p):
            out = attn.apply({"params": p}, x, pad, True, fused)
            return jnp.sum(out * w * real), out  # no cotangent on pad rows, as in the model
        (_, out), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
        return out, grads

    out_xla, g_xla = run(None)
    out_fused, g_fused = run(True)
    assert bool(jnp.all(jnp.isfinite(out_fused)))  # pad rows too
    close = lambda a, b: float(jnp.max(jnp.abs(a - b))) <= 2e-2 * float(jnp.max(jnp.abs(b)))
    assert close(out_fused * real, out_xla * real)
    for name in ("query", "key", "value"):
        assert close(g_fused[name]["kernel"], g_xla[name]["kernel"]), name
    # softmax does not see a shift of every key: the key bias has no gradient
    assert close(g_fused["query"]["bias"], g_xla["query"]["bias"])
    assert close(g_fused["value"]["bias"], g_xla["value"]["bias"])
    assert float(jnp.max(jnp.abs(g_fused["key"]["bias"]))) < 2e-2 * float(
        jnp.max(jnp.abs(g_fused["query"]["bias"])))


def _grad_program(cfg, s, deterministic=True):
    """The lowered backward-and-forward of an encoder apply, as text."""
    enc = RobertaEncoder(cfg)
    ids = jnp.ones((2, s), jnp.int32)
    pad = jnp.ones((2, s), bool).at[0, :5].set(False)
    params = jax.eval_shape(lambda: enc.init(jax.random.key(0), ids, pad))["params"]

    def loss(p, ids, pad):
        kw = {} if deterministic else {"rngs": {"dropout": jax.random.key(1)}}
        return jnp.sum(enc.apply({"params": p}, ids, pad, deterministic=deterministic, **kw)[:, 0])

    return jax.jit(jax.grad(loss)).lower(params, ids, pad).as_text()


def _program_without_kernels(cfg, s, deterministic=True):
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(dispatch, "device_mode", lambda: None)
        return _grad_program(cfg, s, deterministic)


def test_short_sequences_keep_the_einsum_softmax_program(interpreted):
    cfg = _cfg()
    text = _grad_program(cfg, 16)
    assert text == _program_without_kernels(cfg, 16)
    assert "tensor<2x4x16x16xf32>" in text


def test_active_attention_dropout_keeps_the_einsum_softmax_program(interpreted):
    """A fused kernel cannot draw flax's mask: the published 0.1 trains through
    the XLA ops; the same rate under ``deterministic`` draws nothing and fuses."""
    cfg = _cfg(attention_probs_dropout_prob=0.1)
    text = _grad_program(cfg, 128, deterministic=False)
    assert text == _program_without_kernels(cfg, 128, deterministic=False)
    assert "tensor<2x4x128x128xf32>" in text
    assert "tensor<2x4x128x128xf32>" not in _grad_program(cfg, 128, deterministic=True)


def test_off_the_tpu_nothing_interprets_unasked():
    """No patch here: the CPU has no kernel, whatever the shape allows."""
    assert dispatch.device_mode() is None
    cfg = _cfg()
    assert flash.supports(128, cfg.num_attention_heads, cfg.head_dim)
    text = _grad_program(cfg, 128)
    assert text == _program_without_kernels(cfg, 128)
    assert "tensor<2x4x128x128xf32>" in text and "flash_attention" not in text


def _arrays_outside_kernels(jaxpr):
    """Every array a jaxpr names, its sub-jaxprs' too, but for the bodies of
    Pallas calls: what may pass through HBM."""
    for v in [*jaxpr.invars, *jaxpr.constvars, *(o for e in jaxpr.eqns for o in e.outvars)]:
        if hasattr(v.aval, "shape"):
            yield v.aval
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _arrays_outside_kernels(sub)


def test_no_score_sized_array_outside_the_kernels(interpreted):
    """A ``tiny_roberta`` train step at s = 128: neither an operand nor a
    residual of any dtype has two trailing ``s`` axes over batch and heads; the
    XLA path, counted the same way, has them."""
    b, s = 2, 128
    cfg = _cfg()
    enc = RobertaEncoder(cfg)
    ids = jnp.ones((b, s), jnp.int32)
    pad = jnp.ones((b, s), bool).at[0, :5].set(False)
    params = enc.init(jax.random.key(0), ids, pad)["params"]
    # a function of its own each time: jax keeps a traced one, whatever was patched since
    arrays = lambda: list(_arrays_outside_kernels(jax.make_jaxpr(jax.value_and_grad(
        lambda p: jnp.sum(enc.apply({"params": p}, ids, pad)[:, 0] ** 2)))(params).jaxpr))
    score_sized = lambda avals: [
        a for a in avals
        if a.shape[-2:] == (s, s) and a.size >= b * cfg.num_attention_heads * s * s]
    fused = arrays()
    assert len(fused) > 100 and not score_sized(fused)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(dispatch, "device_mode", lambda: None)
        assert score_sized(arrays())


def test_the_kernel_refuses_a_shape_it_does_not_tile():
    assert not flash.supports(96, 4, 64)       # not whole 128-row blocks
    assert not flash.supports(4096, 4, 64)     # a row of keys beyond one tile
    assert not flash.supports(128, 3, 64)      # three 64-wide heads: a half-filled lane block
    assert not flash.supports(128, 4, 48)      # 48 does not divide 128 lanes
    assert flash.supports(512, 12, 64) and flash.supports(128, 2, 128) and flash.supports(256, 8, 16)
    x = jnp.zeros((1, 96, 256), jnp.float32)
    with pytest.raises(ValueError, match="takes no"):
        flash.flash_attention(x, x, x, jnp.ones((1, 96), jnp.int32), num_heads=4, interpret=True)


@pytest.mark.parametrize("heads,head_dim,block_q", [(2, 128, 128), (8, 32, 256), (4, 64, 64)])
def test_flash_attention_over_query_blocks_and_head_widths(heads, head_dim, block_q):
    """dk, dv gathered over several query blocks; a head a lane block and four
    heads a lane block; against the softmax written out."""
    b, s = 2, 256
    kq, kk, kv, kw = jax.random.split(jax.random.key(heads), 4)
    q, k, v, w = (jax.random.normal(key, (b, s, heads * head_dim), jnp.float32)
                  for key in (kq, kk, kv, kw))
    real = jnp.asarray(np.arange(s)[None, :] >= np.array([[0], [s - 8]]))
    w = w * real[:, :, None]

    def written_out(q, k, v):
        q, k, v = (t.reshape(b, s, heads, head_dim) for t in (q, k, v))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(head_dim)
        scores = scores + jnp.where(real[:, None, None, :], 0.0, -1e9)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v).reshape(b, s, -1)

    fused = lambda q, k, v: flash.flash_attention(
        q, k, v, real.astype(jnp.int32), num_heads=heads,
        block_q=block_q, interpret=True)
    grads = lambda f: jax.jit(jax.value_and_grad(
        lambda q, k, v: jnp.sum(f(q, k, v) * w), argnums=(0, 1, 2)))(q, k, v)
    (_, want), (_, got) = grads(written_out), grads(fused)
    for a, e in zip(got, want):
        assert float(jnp.max(jnp.abs(a - e))) <= 2e-2 * float(jnp.max(jnp.abs(e)))


def test_the_backward_keeps_its_accuracy_where_rows_resemble_each_other():
    """Keys and values with a large part in common (what LayerNorm's output
    gives every token) and a cotangent on one row (the last layer's: only
    ``<s>`` is read): ``dq`` is the covariance of two small variations, and an
    error of 2^-9 in the row sum ``sum_k P dP`` is amplified by both. Taken
    over the tile it stays at what bfloat16 operands give XLA's backward
    (0.14 here); ``rowsum(o * do)`` in its place reads 7.9."""
    s, heads, d = 512, 2, 64
    ks = jax.random.split(jax.random.key(0), 6)
    q = 0.3 * jax.random.normal(ks[0], (1, s, heads * d))
    k = 2 * jax.random.normal(ks[5], (1, 1, heads * d)) + 0.1 * jax.random.normal(ks[1], (1, s, heads * d))
    v = 3 * jax.random.normal(ks[2], (1, 1, heads * d)) + 0.1 * jax.random.normal(ks[3], (1, s, heads * d))
    w = jnp.zeros((1, s, heads * d)).at[0, 0].set(jax.random.normal(ks[4], (heads * d,)))
    ones = jnp.ones((1, s), jnp.int32)

    def written_out(q):
        split = lambda t: t.reshape(1, s, heads, d)
        scores = jnp.einsum("bqhd,bkhd->bhqk", split(q), split(k)) / np.sqrt(d)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), split(v)).reshape(1, s, -1)

    want = jax.grad(lambda q: jnp.sum(written_out(q) * w))(q)[0, 0]
    got = jax.grad(lambda q: jnp.sum(flash.flash_attention(
        q, k, v, ones, num_heads=heads, interpret=True) * w))(q)[0, 0]
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 0.3


@pytest.fixture(scope="module")
def tiny_trainer():
    """A LineVul trainer over a two-layer encoder at block 128."""
    from deepdfa_tpu.llm.dataset import HashTokenizer, encode_functions, text_batches
    from deepdfa_tpu.llm.fusion import FusionModel
    from deepdfa_tpu.llm.joint import JointConfig, JointTrainer

    cfg = _cfg(vocab_size=256)
    enc = RobertaEncoder(cfg)
    jcfg = JointConfig(block_size=128, train_batch_size=2, eval_batch_size=2, epochs=1,
                       train_llm=True, use_gnn=False, first_eval_steps=100)
    funcs = [f"int f{i}(int a) {{ return a + {i}; }}" * (1 + i % 3) for i in range(4)]
    examples = encode_functions(funcs, [0, 1, 0, 1], HashTokenizer(vocab_size=cfg.vocab_size),
                                jcfg.block_size, indices=list(range(4)))
    fusion = FusionModel(gnn_cfg=None, input_dim=8, llm_hidden_size=cfg.hidden_size,
                         use_gnn=False, pool="cls")
    ids = jnp.zeros((2, jcfg.block_size), jnp.int32)
    enc_params = enc.init(jax.random.key(0), ids, jnp.ones(ids.shape, bool))["params"]

    def make():
        trainer = JointTrainer(llm=enc, llm_params=enc_params, fusion=fusion, cfg=jcfg, join=None)
        first = trainer._joined(next(text_batches(examples, jcfg.train_batch_size)))
        return trainer, trainer._build(2, first)

    return make, examples, enc, enc_params


@pytest.mark.parametrize("kernel,fused_layers", [(True, 2), (None, 0)])
def test_the_step_says_on_loss_sync_which_attention_it_ran(tiny_trainer, monkeypatch, kernel, fused_layers):
    from deepdfa_tpu.obs import Tracer, TrainTelemetry

    monkeypatch.setattr(dispatch, "device_mode", lambda: kernel)
    make, examples, _, _ = tiny_trainer
    trainer, state = make()
    trainer.telemetry = TrainTelemetry(tracer=Tracer(proc="train", max_spans=256))
    trainer.train(examples, examples, state=state)
    syncs = [s for s in trainer.telemetry.tracer.spans() if s.name == "loss.sync"]
    assert len(syncs) == 2
    for span in syncs:
        assert span.attrs["attn_layers"] == 2 and span.attrs["attn_fused"] == fused_layers
    assert all(np.isfinite(e["train_loss"]) for e in trainer.history if "train_loss" in e)


def test_an_apply_without_the_stats_collection_returns_what_it_did(tiny_trainer, interpreted):
    """Evaluation, ``JointEngine`` and the parity tests apply the encoder
    plainly: hidden states alone, no ``stats`` among ``init``'s variables, and
    the values of the apply that collects them."""
    _, _, enc, params = tiny_trainer
    ids = jax.random.randint(jax.random.key(2), (2, 128), 0, 256)
    pad = jnp.ones((2, 128), bool).at[1, :40].set(False)
    assert set(enc.init(jax.random.key(0), ids, pad)) == {"params"}
    plain = enc.apply({"params": params}, ids, pad)
    assert isinstance(plain, jax.Array) and plain.shape == (2, 128, 256)
    hidden, sown = enc.apply({"params": params}, ids, pad, mutable=["stats"])
    assert np.array_equal(np.asarray(hidden), np.asarray(plain))
    assert {k: int(v) for k, v in sown["stats"]["attn"].items()} == {"layers": 2, "fused": 2}
