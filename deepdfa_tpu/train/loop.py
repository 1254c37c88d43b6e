"""Training/eval steps and the epoch loop for the GGNN classifier.

Covers the reference's Lightning ``BaseModule`` semantics
(``DDFA/code_gnn/models/base_module.py``) rebuilt as pure JAX:

- label extraction per ``label_style`` (graph / node / dataflow_solution_in /
  dataflow_solution_out — ``base_module.py:83-95``), with **masked** segment
  reductions: empty padded graph slots get label 0 and weight 0 (the DGL path
  never saw padding, ours must mask it).
- ``BCEWithLogitsLoss(pos_weight=...)`` (``base_module.py:72-74``).
- node-level undersampled loss (``base_module.py:97-137``): the reference
  samples an exact count of non-vul node indices per batch — a dynamic shape.
  TPU version: Bernoulli mask with matching expected count, which keeps
  shapes static; the loss is reweighted identically in expectation.
- ``cut_nodef`` masking for dataflow-label training (``base_module.py:148-155``).
- metric accumulation inside the jitted step (no per-batch host sync).

Everything here is single-device; the multi-device wrapper lives in
``deepdfa_tpu/parallel``.
"""

from __future__ import annotations

import dataclasses
import logging
import math
from functools import partial
from typing import Any, Callable, Iterable, NamedTuple

import jax
import jax.numpy as jnp
import optax

from deepdfa_tpu.config import ExperimentConfig
from deepdfa_tpu.resilience import faults
from deepdfa_tpu.data.graphs import BatchedGraphs
from deepdfa_tpu.models.ggnn import GGNN
from deepdfa_tpu.obs.tracing import no_span
from deepdfa_tpu.ops.segment import segment_max
from deepdfa_tpu.train.metrics import ConfusionState, compute_metrics, update_confusion

logger = logging.getLogger("deepdfa_tpu")

__all__ = [
    "TrainState",
    "graph_labels",
    "extract_labels",
    "bce_sums",
    "bce_with_logits",
    "make_train_step",
    "make_eval_step",
    "Trainer",
]


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    rng: jax.Array
    step: jnp.ndarray


def graph_labels(batch) -> jnp.ndarray:
    """Graph-level label = max of node ``_VULN`` per graph
    (``base_module.py:86-88``). Empty padded slots → 0 (they carry 0 weight
    anyway, but a finite value keeps the loss NaN-free).

    Works on both layouts: segment (:class:`BatchedGraphs`, flat nodes +
    ``node_gidx``) and dense (:class:`deepdfa_tpu.data.dense.DenseBatch`,
    ``[G, n]`` nodes + ``node_mask``) — the only layout-specific piece of
    the train/eval steps, so :class:`Trainer` drives either forward."""
    vuln = batch.node_feats["_VULN"].astype(jnp.float32)
    if not hasattr(batch, "node_gidx"):  # dense layout
        return jnp.max(jnp.where(batch.node_mask, vuln, 0.0), axis=1)
    # _VULN ∈ {0,1}; empty-segment identity is -inf, so clamp at 0.
    # node_gidx is non-decreasing by construction (batch_np) → sorted fast path
    return jnp.maximum(
        segment_max(vuln, batch.node_gidx, batch.max_graphs,
                    indices_are_sorted=True),
        0.0,
    )


def extract_labels(
    batch: BatchedGraphs, label_style: str
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Return (labels, weights) for the given style; weights exclude padding
    (and non-definition nodes for dataflow_solution_in, parity ``cut_nodef``).
    """
    if label_style == "graph":
        return graph_labels(batch), batch.graph_mask.astype(jnp.float32)
    if label_style == "node":
        labels = batch.node_feats["_VULN"].astype(jnp.float32)
        return labels, batch.node_mask.astype(jnp.float32)
    if label_style in ("dataflow_solution_in", "dataflow_solution_out"):
        key = "_DF_IN" if label_style.endswith("_in") else "_DF_OUT"
        labels = batch.node_feats[key].astype(jnp.float32)
        weights = batch.node_mask.astype(jnp.float32)
        if label_style.endswith("_in"):
            # cut_nodef: only definition nodes (nonzero abstract-dataflow id)
            # contribute (base_module.py:148-155).
            feat_key = (
                "_ABS_DATAFLOW"
                if "_ABS_DATAFLOW" in batch.node_feats
                else "_ABS_DATAFLOW_datatype"
            )
            weights = weights * (batch.node_feats[feat_key] != 0).astype(jnp.float32)
        return labels, weights
    raise NotImplementedError(label_style)


def bce_sums(
    logits: jnp.ndarray,
    labels: jnp.ndarray,
    weights: jnp.ndarray,
    pos_weight: float | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Sum-form BCE-with-logits: ``(Σ per·w, Σ w)``. The sum form is what
    cross-device reductions need (psum numerator and denominator separately,
    then divide) — both the single-device mean and the dp loss derive from it.
    torch ``BCEWithLogitsLoss`` semantics incl. ``pos_weight`` scaling of the
    positive term."""
    log_p = jax.nn.log_sigmoid(logits)
    log_not_p = jax.nn.log_sigmoid(-logits)
    pw = 1.0 if pos_weight is None else pos_weight
    per = -(pw * labels * log_p + (1.0 - labels) * log_not_p)
    return jnp.sum(per * weights), jnp.sum(weights)


def bce_with_logits(
    logits: jnp.ndarray,
    labels: jnp.ndarray,
    weights: jnp.ndarray,
    pos_weight: float | None = None,
) -> jnp.ndarray:
    """Weighted-mean BCE-with-logits (see :func:`bce_sums`)."""
    num, den = bce_sums(logits, labels, weights, pos_weight)
    return num / jnp.maximum(den, 1.0)


def _node_loss_undersample_weights(
    rng: jax.Array, labels: jnp.ndarray, weights: jnp.ndarray, factor: float
) -> jnp.ndarray:
    """Static-shape analogue of ``BaseModule.resample``: keep all positive
    nodes, keep each negative with prob ``factor * n_pos / n_neg``."""
    n_pos = jnp.sum(weights * labels)
    n_neg = jnp.maximum(jnp.sum(weights * (1.0 - labels)), 1.0)
    p_keep = jnp.clip(factor * n_pos / n_neg, 0.0, 1.0)
    keep = jax.random.bernoulli(rng, p_keep, labels.shape).astype(jnp.float32)
    return weights * jnp.where(labels > 0, 1.0, keep)


def make_train_step(
    model: GGNN,
    optimizer: optax.GradientTransformation,
    label_style: str = "graph",
    pos_weight: float | None = None,
    undersample_node_on_loss_factor: float | None = None,
    sentinel_guard: bool = True,
) -> Callable:
    """Build the jitted train step: forward, masked loss, grads, update,
    in-step metric accumulation.

    ``sentinel_guard`` (the in-jit half of the divergence sentinel,
    :mod:`deepdfa_tpu.resilience.sentinel`): when the loss or ANY gradient
    leaf is non-finite the step keeps the previous params/opt-state/metrics
    and reports its loss as NaN — the host detects the skipped step from
    the NaN loss alone (covering the grads-NaN-but-loss-finite case) with
    no extra device sync. The optional trailing ``loss_scale`` argument
    (default 1.0, exact under IEEE) exists for the ``step.nan_grads`` fault
    point: scaling the loss poisons every gradient through the chain rule.
    """

    def loss_fn(params, batch, rng, loss_scale):
        logits = model.apply({"params": params}, batch)
        labels, weights = extract_labels(batch, label_style)
        if label_style == "node" and undersample_node_on_loss_factor is not None:
            weights = _node_loss_undersample_weights(
                rng, labels, weights, undersample_node_on_loss_factor
            )
        loss = bce_with_logits(logits, labels, weights, pos_weight) * loss_scale
        return loss, (logits, labels, weights)

    @jax.jit
    def train_step(
        state: TrainState,
        batch: BatchedGraphs,
        metrics: ConfusionState,
        loss_scale: float = 1.0,
    ):
        rng, sub = jax.random.split(state.rng)
        (loss, (logits, labels, weights)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state.params, batch, sub, loss_scale)
        updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        probs = jax.nn.sigmoid(logits)
        new_metrics = update_confusion(metrics, probs, labels, weights > 0)
        if sentinel_guard:
            good = jnp.isfinite(loss)
            for g in jax.tree.leaves(grads):
                good = good & jnp.all(jnp.isfinite(g))
            sel = lambda new, old: jnp.where(good, new, old)
            params = jax.tree.map(sel, params, state.params)
            opt_state = jax.tree.map(sel, opt_state, state.opt_state)
            new_metrics = jax.tree.map(sel, new_metrics, metrics)
            loss = jnp.where(good, loss, jnp.nan)
        new_state = TrainState(params, opt_state, rng, state.step + 1)
        return new_state, new_metrics, loss, jnp.sum(weights)

    return train_step


def make_eval_step(
    model: GGNN, label_style: str = "graph", pos_weight: float | None = None
) -> Callable:
    @jax.jit
    def eval_step(params, batch: BatchedGraphs, metrics: ConfusionState):
        logits = model.apply({"params": params}, batch)
        labels, weights = extract_labels(batch, label_style)
        loss = bce_with_logits(logits, labels, weights, pos_weight)
        probs = jax.nn.sigmoid(logits)
        metrics = update_confusion(metrics, probs, labels, weights > 0)
        return metrics, loss, probs, labels, weights

    return eval_step


def _weighted_mean(losses: list, wsums: list) -> float:
    """Per-example mean over the epoch: per-batch means re-weighted by their
    real (masked-in) example counts, matching the reference's batch_size-
    weighted Lightning loss logging (``base_module.py:139-146``). The greedy
    packer emits a ragged final batch, so an unweighted mean would be biased.

    Non-finite batch losses are excluded: a sentinel-skipped step reports
    NaN by contract (no update was applied) and must not poison the epoch
    mean."""
    pairs = [
        (float(l), float(w))
        for l, w in zip(losses, wsums)
        if math.isfinite(float(l))
    ]
    total_w = sum(w for _, w in pairs)
    if total_w == 0:
        return 0.0
    return float(sum(l * w for l, w in pairs)) / total_w


@dataclasses.dataclass
class Trainer:
    """Minimal epoch driver; the full-featured CLI trainer (checkpointing,
    logging, profiling — parity with ``main_cli.py``) composes this.

    Layout-polymorphic: ``model`` may be the segment-layout :class:`GGNN`
    or the fused-kernel :class:`~deepdfa_tpu.models.ggnn_fused.GGNNFused`
    (both fed :class:`BatchedGraphs`), or the dense-layout
    :class:`~deepdfa_tpu.models.ggnn_dense.GGNNDense` fed
    :class:`~deepdfa_tpu.data.dense.DenseBatch` — label extraction is the
    only layout-aware step (:func:`graph_labels`)."""

    model: GGNN
    cfg: ExperimentConfig
    pos_weight: float | None = None
    # divergence-rollback LR escalation state: the effective learning rate
    # is optim.lr * lr_scale (see rescale_lr)
    lr_scale: float = 1.0
    # train steps of the most recent epoch that ran on the segment twin
    # instead of the configured layout (steps_for refused the batch's
    # shape) — journaled per epoch so `layout=fused` never silently trains
    # on the twin
    twin_routed_steps: int = 0

    def __post_init__(self):
        self._build()

    def _build(self):
        o = self.cfg.optim
        tx = optax.adamw(o.lr * self.lr_scale, weight_decay=o.weight_decay)
        if o.grad_clip:
            tx = optax.chain(optax.clip_by_global_norm(o.grad_clip), tx)
        self.optimizer = tx
        res = getattr(self.cfg, "resilience", None)
        sentinel_guard = res.sentinel if res is not None else True
        self.train_step = make_train_step(
            self.model,
            self.optimizer,
            label_style=self.cfg.model.label_style,
            pos_weight=self.pos_weight if o.use_weighted_loss else None,
            undersample_node_on_loss_factor=o.undersample_node_on_loss_factor,
            sentinel_guard=sentinel_guard,
        )
        self.eval_step = make_eval_step(
            self.model,
            label_style=self.cfg.model.label_style,
            pos_weight=self.pos_weight if o.use_weighted_loss else None,
        )
        # dense layout: graphs over the per-graph node budget are scored by
        # the segment-layout twin with the SAME params (identical tree,
        # parity-tested) — eval completeness, not a second model. jit is
        # lazy, so the fallback steps cost nothing unless an oversize batch
        # actually arrives. fused layout: same twin, different trigger — a
        # bucket whose VMEM working set exceeds the kernel's planning cap
        # (e.g. the worst-case overflow rescue bucket) takes the segment
        # steps instead; correctness is never gated on VMEM.
        self.fallback_train_step = self.fallback_eval_step = None
        self._seg_twin = None
        if self.cfg.model.layout in ("dense", "fused", "megabatch"):
            import dataclasses as _dc

            from deepdfa_tpu.models import make_model

            seg_twin = self._seg_twin = make_model(
                _dc.replace(self.cfg.model, layout="segment"),
                input_dim=self.model.input_dim,
            )
            self.fallback_train_step = make_train_step(
                seg_twin,
                self.optimizer,
                label_style=self.cfg.model.label_style,
                pos_weight=self.pos_weight if o.use_weighted_loss else None,
                undersample_node_on_loss_factor=o.undersample_node_on_loss_factor,
                sentinel_guard=sentinel_guard,
            )
            self.fallback_eval_step = make_eval_step(
                seg_twin,
                label_style=self.cfg.model.label_style,
                pos_weight=self.pos_weight if o.use_weighted_loss else None,
            )

    def rescale_lr(self, factor: float) -> float:
        """Divergence-rollback escalation: rebuild the optimizer and every
        jitted step at ``optim.lr * lr_scale * factor``. adamw's state tree
        is LR-independent (the rate only scales the applied update), so a
        checkpointed/restored opt_state remains valid under the rescaled
        optimizer. Returns the new cumulative scale."""
        self.lr_scale *= float(factor)
        self._build()
        return self.lr_scale

    def steps_for(self, batch) -> tuple[Callable, Callable]:
        """(train_step, eval_step) for this batch's layout."""
        is_segment = hasattr(batch, "node_gidx")
        if is_segment and self.fallback_train_step is not None:
            if self.cfg.model.layout == "fused":
                # fused consumes segment batches natively; only buckets whose
                # static shape blows the VMEM plan drop to the segment twin.
                # Inside the fused step the backward degrades independently:
                # buckets admitted by fits_vmem_train run the Pallas training
                # kernel (fwd + recompute-bwd as two resident launches inside
                # the one jitted dispatch), the rest recompute through XLA —
                # either way the in-jit sentinel guard and loss_scale
                # semantics of make_train_step apply unchanged.
                from deepdfa_tpu.ops.fused_ggnn import fits_vmem

                if fits_vmem(
                    batch.node_mask.shape[0],
                    batch.senders.shape[0],
                    self.cfg.model.out_dim // 2,
                ):
                    return self.train_step, self.eval_step
            elif self.cfg.model.layout == "megabatch":
                # megabatch consumes segment batches natively; only shapes
                # whose whole-model VMEM plan is refused drop to the segment
                # twin. (The model's own over-plan path computes the same
                # bit-identical segment math, but routing through the twin's
                # steps keeps the compiled-step cache per-layout and the
                # dispatch accounting honest.)
                if self.model.plan_for(
                    batch.node_mask.shape[0],
                    batch.senders.shape[0],
                    batch.graph_mask.shape[0],
                ).fits:
                    return self.train_step, self.eval_step
            return self.fallback_train_step, self.fallback_eval_step
        return self.train_step, self.eval_step

    def init_state(self, example_batch: BatchedGraphs) -> TrainState:
        rng = jax.random.key(self.cfg.seed)
        rng, init_rng = jax.random.split(rng)
        model = self.model
        if (
            hasattr(example_batch, "node_gidx")
            and self._seg_twin is not None
            and self.cfg.model.layout == "dense"
        ):
            # layouts share one param tree, so a segment example initialises
            # the dense model too (possible when every sampled graph was
            # oversize and only the fallback route produced a batch); the
            # fused model consumes segment batches natively, no twin needed
            model = self._seg_twin
        params = model.init(init_rng, example_batch)["params"]
        return TrainState(params, self.optimizer.init(params), rng, jnp.zeros((), jnp.int32))

    def _stream(self, batches: Iterable[BatchedGraphs], telemetry=None):
        """Host→device prefetch for every consumer (train/eval/test): the
        background thread stages the next ``data.prefetch`` batches on
        device while the current step runs — the reference's DataLoader
        ``train_workers`` analogue (``datamodule.py:110-129``)."""
        from deepdfa_tpu.data.prefetch import prefetch_to_device

        size = getattr(self.cfg.data, "prefetch", 2)
        if telemetry is None:
            return prefetch_to_device(batches, size=size)
        return prefetch_to_device(
            batches, size=size, tracer=telemetry.tracer,
            on_span=telemetry.observe_producer,
        )

    def train_epoch(
        self,
        state: TrainState,
        batches: Iterable[BatchedGraphs],
        sentinel=None,
        preemption=None,
        skip_steps: int = 0,
        watchdog=None,
        telemetry=None,
    ) -> tuple[TrainState, dict[str, float], float]:
        """One pass. ``sentinel``: an optional
        :class:`~deepdfa_tpu.resilience.sentinel.DivergenceSentinel`
        observing every per-step loss — it raises ``DivergenceError`` after
        ``patience`` consecutive skipped (non-finite) steps so the caller
        can roll back to the last good checkpoint. The ``step.nan_grads``
        fault point poisons selected steps' gradients via the step's
        ``loss_scale`` argument (chaos battery).

        ``preemption``: an optional
        :class:`~deepdfa_tpu.resilience.preemption.PreemptionHandler`
        whose flag is observed at every step boundary — once set (a real
        SIGTERM/SIGUSR1, or the ``preempt.sigterm`` fault firing) the loop
        raises :class:`~deepdfa_tpu.resilience.preemption.Preempted`
        carrying the current state and the number of batches consumed this
        epoch, so the caller can emergency-checkpoint and exit resumable.

        ``skip_steps``: fast-forward past the first N batches of the
        (deterministic) stream without executing them — the mid-epoch
        resume path after a preemption; the carried rng/params make the
        continuation bit-identical to the uninterrupted epoch.

        ``watchdog``: an optional
        :class:`~deepdfa_tpu.resilience.watchdog.HangWatchdog`; every step
        dispatch runs under its deadline, and the ``step.hang`` fault
        injects a cancel-aware wedge the watchdog must convert into a
        bounded :class:`WatchdogTimeout` (armed ``step.hang`` without a
        watchdog is a no-op — a test must never actually hang)."""
        metrics = ConfusionState.zeros()
        losses, wsums = [], []
        nan_armed = faults.active("step.nan_grads")
        pre_armed = preemption is not None and faults.active("preempt.sigterm")
        hang_armed = watchdog is not None and faults.active("step.hang")
        consumed = 0
        self.twin_routed_steps = 0
        # telemetry (obs.TrainTelemetry) is timing-only: it must not touch
        # batches, rng, or step order, so a telemetered epoch stays
        # bit-identical to a bare one (the elasticity invariants depend on
        # that). Its tracer hangs every step's spans, and the prefetch
        # producer's, under one epoch root.
        tracer = telemetry.tracer if telemetry is not None else None
        span = tracer.span if tracer is not None else no_span
        stream = None
        try:
            with span("train.epoch", root=True):
                stream = self._stream(batches, telemetry)
                it = iter(stream)
                while True:
                    with span("data.wait", step=consumed) as wait:
                        batch = next(it, None)
                    if batch is None:
                        break
                    if consumed < skip_steps:
                        consumed += 1
                        continue
                    if pre_armed and faults.fire("preempt.sigterm"):
                        if telemetry is not None:
                            telemetry.record_event(
                                "fault.fired", point="preempt.sigterm",
                                step=consumed)
                        preemption.trigger("injected fault preempt.sigterm")
                    if preemption is not None and preemption.triggered:
                        from deepdfa_tpu.resilience.preemption import Preempted

                        raise Preempted(
                            state, consumed, preemption.reason or "preempted"
                        )
                    batch = jax.tree.map(jnp.asarray, batch)
                    step, _ = self.steps_for(batch)
                    if step is self.fallback_train_step:
                        if not self.twin_routed_steps:
                            logger.warning(
                                "layout=%s: batch shape (%d nodes, %d edges) "
                                "is outside the layout's plan — this step "
                                "trains on the segment twin (counted as "
                                "twin_routed_steps in the journal)",
                                self.cfg.model.layout,
                                batch.node_mask.shape[0],
                                batch.senders.shape[0])
                        self.twin_routed_steps += 1
                    if hang_armed and faults.fire("step.hang"):
                        # simulated wedged dispatch: parks until the
                        # watchdog's deadline cancels it → WatchdogTimeout,
                        # thread unwinds
                        if telemetry is not None:
                            telemetry.record_event(
                                "fault.fired", point="step.hang",
                                step=consumed)
                        watchdog.call(
                            "train_step",
                            lambda cancel: cancel.wait(),
                            cancel_aware=True,
                        )
                    nan_fired = nan_armed and faults.fire("step.nan_grads")
                    if nan_fired and telemetry is not None:
                        telemetry.record_event(
                            "fault.fired", point="step.nan_grads",
                            step=consumed)
                    args = (
                        (state, batch, metrics, float("nan"))
                        if nan_fired
                        else (state, batch, metrics)
                    )
                    with span("step.dispatch", step=consumed) as dispatch:
                        if watchdog is not None:
                            state, metrics, loss, wsum = watchdog.call(
                                "train_step", step, *args
                            )
                        else:
                            state, metrics, loss, wsum = step(*args)
                    consumed += 1
                    if telemetry is not None:
                        telemetry.observe_step(wait.dur_s, dispatch.dur_s)
                    if sentinel is not None:
                        sentinel.observe(loss)
                    losses.append(loss)
                    wsums.append(wsum)
                if sentinel is not None:
                    sentinel.flush()
                # the host-side reduction below is where the epoch's async
                # dispatches actually block — the device.sync span
                with span("device.sync", n_steps=consumed):
                    return (state, compute_metrics(metrics, "train_"),
                            _weighted_mean(losses, wsums))
        finally:
            # deterministic producer shutdown even when the sentinel raises
            # mid-epoch (prefetch_to_device joins its thread on close)
            if stream is not None:
                stream.close()

    def evaluate(
        self, params, batches: Iterable[BatchedGraphs], prefix: str = "val_"
    ) -> tuple[dict[str, float], float]:
        metrics = ConfusionState.zeros()
        losses, wsums = [], []
        for batch in self._stream(batches):
            batch = jax.tree.map(jnp.asarray, batch)
            _, estep = self.steps_for(batch)
            metrics, loss, _probs, _labels, weights = estep(params, batch, metrics)
            losses.append(loss)
            wsums.append(jnp.sum(weights))
        mean_loss = _weighted_mean(losses, wsums)
        out = compute_metrics(metrics, prefix)
        out[f"{prefix}loss"] = mean_loss
        return out, mean_loss
