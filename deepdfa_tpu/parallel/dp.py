"""Data-parallel training over the ``dp`` mesh axis.

Replaces the reference's device-data-parallel story (Lightning DDP/NCCL when
``trainer.gpus > 1``, ``config_default.yaml:3``; ``torch.nn.DataParallel``,
``MSIVD/msivd/train.py:936``) with SPMD: each ``dp`` shard owns one
fixed-shape :class:`BatchedGraphs`, runs the local forward/backward, and
gradients/losses/metric counts are ``psum``'d over ICI inside the compiled
step — XLA emits the all-reduce, no process groups.

Layout: host stacks ``dp`` same-bucket batches into leading-axis-``dp``
arrays (:func:`stack_batches`); ``shard_map`` splits them back per device.
Graph node indices are local to each shard's batch, so no cross-shard
segment ops exist — the only collectives are the gradient/metric psums.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, PartitionSpec as P

from deepdfa_tpu.data.graphs import BatchedGraphs
from deepdfa_tpu.models.ggnn import GGNN
from deepdfa_tpu.train.loop import TrainState, bce_sums, extract_labels
from deepdfa_tpu.train.metrics import ConfusionState, update_confusion

__all__ = ["stack_batches", "make_dp_train_step", "make_dp_eval_step", "dp_init_state"]


def stack_batches(batches: list) -> BatchedGraphs:
    """Stack ``dp`` same-shape batches along a new leading device axis.
    Works on either layout (:class:`BatchedGraphs` or
    :class:`deepdfa_tpu.data.dense.DenseBatch` — both carry ``node_mask``,
    whose shape identifies the compiled bucket)."""
    shapes = {tuple(np.shape(b.node_mask)) for b in batches}
    if len(shapes) != 1:
        raise ValueError(f"all stacked batches must share one bucket shape, got {shapes}")
    return jax.tree.map(lambda *xs: np.stack(xs, axis=0), *batches)


def _batch_pspecs(batch: BatchedGraphs) -> BatchedGraphs:
    """PartitionSpec pytree: every array sharded on its leading dp axis."""
    return jax.tree.map(lambda _: P("dp"), batch)


def dp_init_state(
    model: GGNN, optimizer: optax.GradientTransformation, example_batch: BatchedGraphs, seed: int = 0
) -> TrainState:
    """Initialise replicated params from one (unstacked) example batch."""
    rng = jax.random.key(seed)
    rng, init_rng = jax.random.split(rng)
    params = model.init(init_rng, example_batch)["params"]
    return TrainState(params, optimizer.init(params), rng, jnp.zeros((), jnp.int32))


def make_dp_train_step(
    model: GGNN,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    label_style: str = "graph",
    pos_weight: float | None = None,
    undersample_node_on_loss_factor: float | None = None,
    donate: bool = True,
    accum: int = 1,
) -> Callable:
    """Compile the SPMD train step.

    Signature of the returned fn: ``(state, stacked_batch, metrics) ->
    (state, metrics, loss)`` where ``stacked_batch`` has a leading ``dp``
    axis. Params/opt-state/metrics are replicated; the gradient all-reduce is
    a single fused psum over ICI.

    With ``donate=True`` BOTH the state (arg 0) and the metrics tree (arg 2)
    are donated: each maps 1:1 onto an output of identical shape/dtype, so
    XLA updates params/opt-state/confusion counters in place instead of
    allocating a second copy. Callers must rebind both from the return value
    (``state, metrics, loss, wsum = step(state, batch, metrics)``) — the
    passed-in buffers are dead after the call.

    ``accum > 1`` enables gradient accumulation for mesh-elastic resume:
    each shard processes ``accum`` microbatches (stacked as ``[dp, accum,
    ...]`` by :func:`deepdfa_tpu.parallel.elastic.stack_elastic`), summing
    loss/weight/gradient contributions before the psum — a ``dp=N/k,
    accum=k`` step consumes the same global batch (and folds the same
    per-batch rng streams: microbatch ``i`` on shard ``j`` uses fold-in
    index ``j*accum + i``) as the original ``dp=N`` step, so metrics match
    up to float reassociation in the reductions.
    """
    if accum < 1:
        raise ValueError("accum must be >= 1")
    from deepdfa_tpu.train.loop import _node_loss_undersample_weights

    def local_loss(params, batch, rng):
        logits = model.apply({"params": params}, batch)
        labels, weights = extract_labels(batch, label_style)
        if label_style == "node" and undersample_node_on_loss_factor is not None:
            weights = _node_loss_undersample_weights(
                rng, labels, weights, undersample_node_on_loss_factor
            )
        # Sum form so the cross-device reduction is exact:
        # total = psum(Σ per·w) / psum(Σ w).
        lsum, _ = bce_sums(logits, labels, weights, pos_weight)
        return lsum, (logits, labels, weights)

    def spmd_step(state: TrainState, batch: BatchedGraphs, metrics: ConfusionState):
        # Per-shard batch arrives with the dp axis split off by shard_map:
        # [1, ...] for accum == 1, [1, accum, ...] for the accumulating step.
        batch = jax.tree.map(lambda x: x[0], batch)
        axis_idx = jax.lax.axis_index("dp")
        rng, sub = jax.random.split(state.rng)
        micros = (
            [batch]
            if accum == 1
            else [jax.tree.map(lambda x: x[i], batch) for i in range(accum)]
        )
        lsum = jnp.zeros(())
        local_w = jnp.zeros(())
        grads = None
        local = ConfusionState.zeros()
        for i, mb in enumerate(micros):
            # fold-in index = the flat batch index this (shard, micro) slot
            # consumes under stack_elastic's layout — identical rng streams
            # whether the batch ran as dp=N or dp=N/k with accum=k
            sub_i = jax.random.fold_in(sub, axis_idx * accum + i)
            (ls, (logits, labels, weights)), g = jax.value_and_grad(
                local_loss, has_aux=True
            )(state.params, mb, sub_i)
            lsum = lsum + ls
            local_w = local_w + jnp.sum(weights)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
            probs = jax.nn.sigmoid(logits)
            local = update_confusion(local, probs, labels, weights > 0)
        grads = jax.lax.psum(grads, "dp")
        lsum = jax.lax.psum(lsum, "dp")
        wsum = jax.lax.psum(local_w, "dp")
        loss = lsum / jnp.maximum(wsum, 1.0)
        # Grads are sums over examples; normalise to the global weighted mean.
        grads = jax.tree.map(lambda g: g / jnp.maximum(wsum, 1.0), grads)
        updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        delta = jax.lax.psum(local, "dp")
        metrics = ConfusionState(*(m + d for m, d in zip(metrics, delta)))
        return TrainState(params, opt_state, rng, state.step + 1), metrics, loss, wsum

    def wrapped(state, stacked_batch, metrics):
        batch_specs = _batch_pspecs(stacked_batch)
        fn = jax.shard_map(
            spmd_step,
            mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), state), batch_specs,
                      jax.tree.map(lambda _: P(), metrics)),
            out_specs=(jax.tree.map(lambda _: P(), state), jax.tree.map(lambda _: P(), metrics), P(), P()),
            check_vma=False,
        )
        return fn(state, stacked_batch, metrics)

    return jax.jit(wrapped, donate_argnums=(0, 2) if donate else ())


def make_dp_eval_step(
    model: GGNN, mesh: Mesh, label_style: str = "graph", pos_weight: float | None = None
) -> Callable:
    def spmd_eval(params, batch: BatchedGraphs, metrics: ConfusionState):
        batch = jax.tree.map(lambda x: x[0], batch)
        logits = model.apply({"params": params}, batch)
        labels, weights = extract_labels(batch, label_style)
        lsum, local_w = bce_sums(logits, labels, weights, pos_weight)
        loss_num = jax.lax.psum(lsum, "dp")
        wsum = jax.lax.psum(local_w, "dp")
        probs = jax.nn.sigmoid(logits)
        local = update_confusion(ConfusionState.zeros(), probs, labels, weights > 0)
        delta = jax.lax.psum(local, "dp")
        metrics = ConfusionState(*(m + d for m, d in zip(metrics, delta)))
        return metrics, loss_num / jnp.maximum(wsum, 1.0), wsum

    def wrapped(params, stacked_batch, metrics):
        fn = jax.shard_map(
            spmd_eval,
            mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), params), _batch_pspecs(stacked_batch),
                      jax.tree.map(lambda _: P(), metrics)),
            out_specs=(jax.tree.map(lambda _: P(), metrics), P(), P()),
            check_vma=False,
        )
        return fn(params, stacked_batch, metrics)

    return jax.jit(wrapped)
