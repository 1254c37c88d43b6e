"""``ops/selective_scan.gated_scan`` as the kernel (the Pallas interpreter
standing in for the chip) against a float64 loop and against its own plain
form: every mask layout, leading pad chunks skipped, bfloat16 operands left
where they are, its gradient; the shapes it does not take; when ``MambaMixer``
takes it, and the counter that says so on ``loss.sync``; and the CodeBERT and
LongCat steps, which share none of it, unchanged."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepdfa_tpu.llm.jamba import JambaModel, tiny_jamba
from deepdfa_tpu.ops import dispatch
from deepdfa_tpu.ops import selective_scan as ss

B, S, D, N = 3, 64, 1024, 16
# first real position a row (left-padded): none padded, a boundary inside a
# chunk of 16, leading chunks skipped whole (48 = three chunks of 16), all pad
FIRST = (0, 21, 48, S)


def _left_padded(first=FIRST, s=S):
    return np.arange(s)[None, :] >= np.asarray(first)[:, None]


def _operands(dtype=np.float32, b=B, s=S, d=D, seed=0, z_width=None):
    rng = np.random.default_rng(seed)
    f = lambda *shape: rng.normal(size=shape).astype(np.float32)
    ops = dict(c=f(b, s, d), dt=f(b, s, d) - 3.0, dt_bias=0.3 * f(d), A=-np.exp(f(d, N)),
               B=f(b, s, N), C=f(b, s, N), D=f(d), z=f(b, s, z_width or d))
    wide = ("c", "dt", "B", "C", "z")
    return {k: jnp.asarray(v, dtype if k in wide else np.float32) for k, v in ops.items()}


def _float64_loop(c, dt, dt_bias, A, B, C, D, z, mask):
    """Softplus, the recurrence position by position, the gate: float64."""
    c, dt, dt_bias, A, B, C, D, z = (np.asarray(v, np.float64) for v in (c, dt, dt_bias, A, B, C, D, z))
    delta = np.logaddexp(dt + dt_bias, 0.0)
    y = np.zeros_like(c)
    for i in range(c.shape[0]):
        state = np.zeros(A.shape)
        for t in range(c.shape[1]):
            x = delta[i, t] * c[i, t] * mask[i, t]
            state = np.exp(delta[i, t][:, None] * A) * state + x[:, None] * B[i, t][None]
            y[i, t] = state @ C[i, t] + D * c[i, t]
    return y * z / (1.0 + np.exp(-z))


@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_the_kernel_matches_a_float64_loop(chunk):
    """Four, two and one chunk a row; rows whose first real token lies inside
    a chunk, on a chunk's edge with the chunks before it skipped, nowhere."""
    ops, mask = _operands(), _left_padded()
    ops["c"] = ops["c"] * mask[: B, :, None]
    got = np.asarray(ss.gated_scan(**ops, mask=jnp.asarray(mask[:B]), interpret=True, chunk=chunk))
    want = _float64_loop(**ops, mask=mask[:B])
    assert got.shape == want.shape and got.dtype == np.float32
    assert np.abs(got - want)[mask[:B]].max() / np.abs(want).max() < 2e-6  # read 2e-7 - 3e-7
    plain = np.asarray(ss.gated_scan(**ops, mask=jnp.asarray(mask[:B])))
    assert np.abs(plain - want)[mask[:B]].max() / np.abs(want).max() < 2e-6


@pytest.mark.parametrize("unroll", [4, 16])
def test_the_trip_of_the_inner_loop_changes_no_number(unroll):
    """To the last bits: the interpreter's XLA contracts a trip's multiplies
    and adds as it sees fit."""
    from deepdfa_tpu.ops.selective_scan_kernel import scan_forward

    ops, mask = _operands(b=2, seed=3), jnp.asarray(_left_padded((5, 40)))
    got = scan_forward(*ops.values(), mask, chunk=32, unroll=unroll, interpret=True)
    want = ss.gated_scan(**ops, mask=mask, interpret=True, chunk=32)  # 8 positions a trip
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 1e-6 * np.abs(np.asarray(want)).max()


MASKS = {
    "no_mask": None,
    "left_padded": _left_padded((0, 21, 48, S)),
    "right_padded": np.arange(S)[None, :] < np.array([[S], [33], [16], [1]]),
    "holes": np.random.default_rng(0).random((4, S)) < 0.5,
}


@pytest.mark.parametrize("layout", MASKS)
def test_real_tokens_are_the_plain_forms_under_any_mask(layout):
    """Only *leading* wholly-pad chunks are skipped; inside a visited chunk a
    pad adds nothing to the state and decays it, so any mask reads what the
    plain form reads at every real token — and ``c`` left unmasked at the pads
    changes no real token at all."""
    ops = _operands(b=4, seed=1)
    mask = None if MASKS[layout] is None else jnp.asarray(MASKS[layout])
    got = np.asarray(ss.gated_scan(**ops, mask=mask, interpret=True, chunk=16))
    want = np.asarray(ss.gated_scan(**ops, mask=mask))
    real = np.ones((4, S), bool) if mask is None else np.asarray(mask)
    assert np.abs(got - want)[real].max() <= 4e-6 * np.abs(want).max()
    if mask is not None:
        noisy = dict(ops, c=ops["c"] + 7.0 * ~real[..., None])
        again = np.asarray(ss.gated_scan(**noisy, mask=mask, interpret=True, chunk=16))
        assert np.array_equal(again[real], got[real])


def test_a_skipped_chunk_returns_zeros_and_an_all_pad_row_nothing_else():
    ops, mask = _operands(), _left_padded()
    got = np.asarray(ss.gated_scan(**ops, mask=jnp.asarray(mask[:B]), interpret=True, chunk=16))
    assert not got[2, :48].any() and got[2, 48:].all()  # three chunks skipped whole
    assert not got[1, :16].any() and got[1, 16:21].any()  # a pad inside a visited chunk is computed
    assert not np.asarray(ss.gated_scan(**_operands(b=1), mask=jnp.zeros((1, S), bool),
                                        interpret=True, chunk=16)).any()


def test_bfloat16_operands_stay_bfloat16():
    """The cell's dtype: nothing of ``[b, s, d]`` is cast, reshaped or
    transposed outside the kernel — its operands are the arrays handed in, ``z``
    the whole of ``in_proj``'s output — and the result is bfloat16, within a
    rounding of the float32 answer."""
    ops = _operands(jnp.bfloat16, b=2, z_width=2 * D)
    mask = jnp.asarray(_left_padded((0, 21)))
    run = lambda ops: ss.gated_scan(**ops, mask=mask, interpret=True, chunk=16)
    got = run(ops)
    assert got.dtype == jnp.bfloat16 and got.shape == (2, S, D)
    wide = {k: v.astype(jnp.float32) for k, v in ops.items()}
    want = np.asarray(ss.gated_scan(**wide, mask=mask))
    err = np.abs(np.asarray(got.astype(jnp.float32)) - want)[np.asarray(mask)]
    assert err.max() <= 2e-2 * np.abs(want).max()

    def eqns(jaxpr):
        for e in jaxpr.eqns:
            inner = [v for v in e.params.values() if hasattr(v, "jaxpr") or hasattr(v, "eqns")]
            if e.primitive.name == "pallas_call" or not inner:
                yield e
            for v in inner:
                yield from eqns(getattr(v, "jaxpr", v))

    found = list(eqns(jax.make_jaxpr(run)(ops).jaxpr))
    call, = [e for e in found if e.primitive.name == "pallas_call"]
    big = lambda v: v.aval.shape[:2] == (2, S) and v.aval.shape[-1] >= D
    assert [v.aval.dtype for v in call.invars if big(v)] == [jnp.bfloat16] * 3
    assert [v.aval.shape for v in call.invars if big(v)] == [(2, S, D), (2, S, D), (2, S, 2 * D)]
    assert not [e.primitive.name for e in found if e is not call and any(map(big, e.outvars))]


def test_gradients_are_the_plain_forms():
    ops = _operands(b=2, seed=2)
    mask = jnp.asarray(_left_padded((0, 21)))
    w = jnp.asarray(np.random.default_rng(9).normal(size=(2, S, D)), jnp.float32)
    through = lambda **kw: jax.grad(
        lambda ops: jnp.sum(ss.gated_scan(**ops, mask=mask, **kw) * w))(ops)
    got, want = through(interpret=True, chunk=16), through()
    for name in ops:
        assert got[name].shape == want[name].shape and float(jnp.abs(want[name]).max()) > 0
        np.testing.assert_allclose(got[name], want[name], atol=1e-6)


def test_shapes_it_does_not_take():
    assert ss.supports(2048, 5120, 16)
    cfg = tiny_jamba()
    assert not ss.supports(32, cfg.d_inner, cfg.mamba_d_state)  # 128 channels, 8 states
    assert not ss.supports(2048 + 8, 5120, 16)  # no whole chunks
    assert not ss.supports(2048, 5120, 8) and not ss.supports(2048, 5120 + 128, 16)
    ops = _operands(b=1)
    with pytest.raises(ValueError, match="takes no"):
        ss.gated_scan(**{**ops, "A": ops["A"][:, :8], "B": ops["B"][..., :8],
                         "C": ops["C"][..., :8]}, interpret=True)
    with pytest.raises(ValueError, match="do not tile"):
        ss.gated_scan(**ops, interpret=True, chunk=24)


# -- behind MambaMixer ---------------------------------------------------------


@pytest.fixture(scope="module")
def decoder():
    """``tiny_jamba`` with the mixer widths the kernel takes (1,024 channels,
    16 states): two Mamba layers and one attention layer, three chunks a row."""
    cfg = tiny_jamba(hidden_size=512, num_hidden_layers=3, mamba_d_state=16, mamba_dt_rank=16)
    model = JambaModel(cfg)
    ids = jax.random.randint(jax.random.key(3), (3, 48), 3, cfg.vocab_size)
    mask = jnp.asarray(_left_padded((0, 19, 47), 48))
    params = model.init(jax.random.key(0), ids, mask)["params"]
    return cfg, model, params, ids, mask


@pytest.mark.parametrize("kernel,fused", [(True, 2), (None, 0)])
def test_the_mixer_takes_the_kernel_where_it_can_run(decoder, monkeypatch, kernel, fused):
    cfg, model, params, ids, mask = decoder
    plain = model.apply({"params": params}, ids, mask)  # the CPU: no kernel
    monkeypatch.setattr(dispatch, "device_mode", lambda: kernel)
    apply = lambda p, i, m: model.apply({"params": p}, i, m, mutable=["stats"])
    hidden, sown = apply(params, ids, mask)
    real, dense = int(mask.sum()), 3 * 48  # 512 does not tile 48: whole rows
    assert jax.device_get(sown["stats"]) == {
        "ssm": {"layers": 2, "fused": fused, "tokens_real": 2 * real, "tokens_dense": 2 * dense},
        "attn": {"layers": 1, "fused": 0, "tokens_real": real, "tokens_dense": dense}}
    assert str(jax.make_jaxpr(apply)(params, ids, mask)).count("selective_scan_fwd") == fused
    real = np.asarray(mask)
    np.testing.assert_allclose(np.asarray(hidden)[real], np.asarray(plain)[real], atol=2e-4)


def test_tiny_jambas_own_shapes_keep_the_plain_form(monkeypatch):
    monkeypatch.setattr(dispatch, "device_mode", lambda: True)
    model = JambaModel(tiny_jamba(num_hidden_layers=3))  # 128 channels, 8 states
    ids = jnp.zeros((1, 32), jnp.int32)
    _, sown = model.apply(model.init(jax.random.key(0), ids), ids, mutable=["stats"])
    assert jax.device_get(sown["stats"]) == {
        "ssm": {"layers": 2, "fused": 0, "tokens_real": 2 * 32, "tokens_dense": 2 * 32},
        "attn": {"layers": 1, "fused": 0, "tokens_real": 32, "tokens_dense": 32}}


@pytest.mark.parametrize("kernel,fused", [(True, 2), (None, 0)])
def test_the_step_says_on_loss_sync_which_scan_it_ran(decoder, monkeypatch, kernel, fused):
    """Through ``JointTrainer.train`` with the decoder frozen: ``ssm_fused``
    is every Mamba layer or none, ``attn_fused`` stays 0."""
    from deepdfa_tpu.llm.dataset import HashTokenizer, encode_functions
    from deepdfa_tpu.llm.fusion import FusionModel
    from deepdfa_tpu.llm.joint import JointConfig, JointTrainer
    from deepdfa_tpu.obs import Tracer, TrainTelemetry

    monkeypatch.setattr(dispatch, "device_mode", lambda: kernel)
    cfg, model, params, _, _ = decoder
    jcfg = JointConfig(block_size=48, train_batch_size=2, eval_batch_size=2, epochs=1,
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
        counts = {k: v for k, v in span.attrs.items() if k.startswith(("ssm_", "attn_"))}
        real = counts.pop("attn_tokens_real")
        assert 0 < real <= 2 * 48 and counts.pop("ssm_tokens_real") == 2 * real
        assert counts.pop("attn_tokens_dense") == 2 * 48  # both rows hold a real token
        assert counts.pop("ssm_tokens_dense") == 2 * 2 * 48
        assert counts == {"ssm_layers": 2, "ssm_fused": fused, "attn_layers": 1, "attn_fused": 0}
    assert all(np.isfinite(e["train_loss"]) for e in trainer.history if "train_loss" in e)


# -- what shares none of it ----------------------------------------------------


@pytest.mark.parametrize("use_gnn", [False, True])
def test_the_codebert_step_is_lowered_as_before(use_gnn):
    """``RobertaEncoder`` and its kernels share no line of this one: the step
    is PR 31's byte for byte (the digests ``test_latent_attention`` keeps)."""
    from test_latent_attention import CODEBERT_STEPS, _lowered_codebert_step

    text = _lowered_codebert_step(use_gnn)
    assert hashlib.sha256(text.encode()).hexdigest() == CODEBERT_STEPS[use_gnn]


def test_the_longcat_step_is_lowered_as_before():
    """Nor does the routed decoder (the digest ``test_pangu_moe`` keeps)."""
    from test_pangu_moe import LONGCAT_STEP, _lowered_longcat_step

    assert hashlib.sha256(_lowered_longcat_step().encode()).hexdigest() == LONGCAT_STEP
