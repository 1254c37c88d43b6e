"""Chaos battery: the fault-tolerance invariants driven end-to-end.

Fast tier (``faults`` marker, in-process): the jitted step's sentinel guard
skips poisoned updates; ``train_epoch`` + ``DivergenceSentinel`` raise on
injected NaN-grad runs and the rollback restore + LR backoff recovers;
checkpoint aux payloads make resume bit-identical to an uninterrupted run.

Slow tier (``slow`` marker, subprocess): ``scripts/chaos_train.py`` — real
``kill -9`` (``os._exit``) mid-checkpoint-commit, then ``fit --resume``
reaching the same final metrics, plus the sentinel run completing through a
rollback."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepdfa_tpu.config import CheckpointConfig, ExperimentConfig, GGNNConfig
from deepdfa_tpu.data.graphs import BucketSpec, GraphBatcher
from deepdfa_tpu.data.synthetic import random_dataset
from deepdfa_tpu.models.ggnn import GGNN
from deepdfa_tpu.resilience import DivergenceError, DivergenceSentinel, faults
from deepdfa_tpu.train.checkpoint import CheckpointManager
from deepdfa_tpu.train.loop import Trainer, TrainState

pytestmark = pytest.mark.faults

REPO = Path(__file__).resolve().parent.parent

SMALL = dict(hidden_dim=8, n_steps=1, num_output_layers=2)


def _setup(n_graphs=24, bucket_graphs=12, seed=3):
    cfg = ExperimentConfig(model=GGNNConfig(**SMALL))
    graphs = random_dataset(n_graphs, seed=seed, input_dim=cfg.input_dim,
                            vul_rate=0.25)
    model = GGNN(cfg=cfg.model, input_dim=cfg.input_dim)
    trainer = Trainer(model=model, cfg=cfg, pos_weight=3.0)
    batches = list(
        GraphBatcher([BucketSpec(bucket_graphs, 2048, 4096)]).batches(graphs)
    )
    state = trainer.init_state(jax.tree.map(jnp.asarray, batches[0]))
    return trainer, state, batches


def _leaves(params):
    return [np.asarray(x) for x in jax.tree.leaves(params)]


def test_sentinel_guard_skips_poisoned_step_in_jit():
    """loss_scale=NaN poisons every gradient; the guarded step must keep
    params/opt-state/metrics and report a NaN loss — and the poisoned call
    must reuse the same compiled executable (weak-typed scalar), not
    recompile."""
    from deepdfa_tpu.train.metrics import ConfusionState

    trainer, state, batches = _setup()
    batch = jax.tree.map(jnp.asarray, batches[0])
    metrics = ConfusionState.zeros()

    new_state, new_metrics, loss, wsum = trainer.train_step(
        state, batch, metrics, float("nan")
    )
    assert not np.isfinite(float(loss))
    assert float(wsum) > 0  # weights are reported regardless
    for a, b in zip(_leaves(state.params), _leaves(new_state.params)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(jax.tree.leaves(metrics), jax.tree.leaves(new_metrics)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # step counter still advances (it indexes the stream, not the update)
    assert int(new_state.step) == int(state.step) + 1

    # a clean step through the same executable updates params again
    ok_state, _, ok_loss, _ = trainer.train_step(new_state, batch, metrics)
    assert np.isfinite(float(ok_loss))
    assert any(
        not np.array_equal(a, b)
        for a, b in zip(_leaves(new_state.params), _leaves(ok_state.params))
    )


def test_nan_grads_fault_drives_sentinel_rollback(tmp_path):
    """The full in-process rollback cycle: clean epoch → checkpoint; armed
    epoch (step.nan_grads on every step, patience 2) → DivergenceError;
    restore last good params + aux, halve LR, re-run clean → completes."""
    trainer, state, batches = _setup()
    ckpts = CheckpointManager(tmp_path / "ck", CheckpointConfig())
    sentinel = DivergenceSentinel(patience=2, lag=1)

    state, m, loss = trainer.train_epoch(state, batches, sentinel=sentinel)
    assert np.isfinite(loss)
    aux = {
        "opt_state": state.opt_state,
        "rng": jax.random.key_data(state.rng),
        "step": state.step,
    }
    ckpts.save(int(state.step), {"params": state.params},
               metrics={"val_loss": float(loss)}, epoch=0, aux=aux)
    good_params = _leaves(state.params)

    with faults.installed("step.nan_grads"):  # every step poisoned
        with pytest.raises(DivergenceError):
            trainer.train_epoch(state, batches, sentinel=sentinel)

    # rollback: restore the committed state, back off the LR, reset sentinel
    step, meta, payload, raux = ckpts.restore_resume(
        template={"params": state.params}, aux_template=aux
    )
    assert meta["epoch"] == 0
    restored = TrainState(
        payload["params"], raux["opt_state"],
        jax.random.wrap_key_data(raux["rng"]), raux["step"],
    )
    for a, b in zip(good_params, _leaves(restored.params)):
        np.testing.assert_array_equal(a, b)
    assert trainer.rescale_lr(0.5) == 0.5
    sentinel.reset()

    state2, _, loss2 = trainer.train_epoch(restored, batches, sentinel=sentinel)
    assert np.isfinite(loss2)
    assert sentinel.stats()["sentinel_bad_steps"] >= 2


def test_checkpoint_resume_is_bit_identical():
    """Epoch 1 → save(+aux) → restore into a FRESH trainer → epoch 2 must
    equal two uninterrupted epochs exactly (params, rng, opt-state)."""
    trainer, state0, batches = _setup()

    # uninterrupted: two epochs straight through
    s, _, _ = trainer.train_epoch(state0, batches)
    s_cont, _, _ = trainer.train_epoch(s, batches)

    # interrupted: re-run epoch 1 from the same init, checkpoint, resume
    trainer_b, state_b, _ = _setup()
    s1, _, _ = trainer_b.train_epoch(state_b, batches)
    aux = {
        "opt_state": s1.opt_state,
        "rng": jax.random.key_data(s1.rng),
        "step": s1.step,
    }
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        ckpts = CheckpointManager(d, CheckpointConfig())
        ckpts.save(int(s1.step), {"params": s1.params},
                   metrics={"val_loss": 1.0}, epoch=0, aux=aux)
        trainer_c, state_c, _ = _setup()  # fresh process stand-in
        step, _meta, payload, raux = ckpts.restore_resume(
            template={"params": state_c.params}, aux_template=aux
        )
    resumed = TrainState(
        payload["params"], raux["opt_state"],
        jax.random.wrap_key_data(raux["rng"]), raux["step"],
    )
    s_res, _, _ = trainer_c.train_epoch(resumed, batches)

    for a, b in zip(_leaves(s_cont.params), _leaves(s_res.params)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(
        jax.random.key_data(s_cont.rng), jax.random.key_data(s_res.rng)
    )


def test_train_epoch_closes_prefetch_on_divergence():
    """The sentinel raising mid-epoch must not leak the prefetch producer
    thread (train_epoch closes the stream in its finally)."""
    import threading

    trainer, state, batches = _setup()
    sentinel = DivergenceSentinel(patience=1, lag=0)
    with faults.installed("step.nan_grads"):
        with pytest.raises(DivergenceError):
            trainer.train_epoch(state, batches * 4, sentinel=sentinel)
    leaked = [
        t for t in threading.enumerate()
        if t.name == "prefetch_to_device" and t.is_alive()
    ]
    assert leaked == []


# ---------------------------------------------------------------------------
# subprocess battery (real kill -9 + resume): slow tier


@pytest.mark.slow
def test_chaos_train_battery(tmp_path):
    """scripts/chaos_train.py end-to-end: crash rc=137 with a .tmp partial,
    resume matches the clean oracle, NaN run completes via rollback."""
    env = dict(os.environ) | {"JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO)}
    proc = subprocess.run(
        [sys.executable, "scripts/chaos_train.py",
         "--workdir", str(tmp_path / "chaos"), "--epochs", "3"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=3000,
    )
    assert proc.returncode == 0, f"stdout={proc.stdout}\nstderr={proc.stderr[-3000:]}"
    verdict = json.loads(proc.stdout.strip().splitlines()[-1])
    assert verdict["ok"], verdict
    assert verdict["crash"]["returncode"] == 137
    assert verdict["crash"]["partial_dirs"]
    assert verdict["resume"]["metric_diffs"]
    assert verdict["sentinel"]["n_rollbacks"] >= 1
