"""Joint LLM + GGNN training — the MSIVD training loop, rebuilt for TPU.

Covers ``MSIVD/msivd/train.py:211-585`` (``train``/``evaluate``/``test``):

- **frozen LLM forward** feeding final hidden states into the trainable
  fusion model (``train.py:324-331``); only fusion params (GGNN + head) get
  gradients — the LLM params enter the jitted step as a constant input, so no
  backward pass is ever built through the decoder stack (the TPU analogue of
  ``self.encoder.eval()`` + optimizer over ``gnn_model`` params only).
- AdamW with **no-decay param groups** (bias / norm scales,
  ``train.py:242-260``) via an ``optax.masked`` weight-decay mask.
- **cosine schedule with linear warmup**, ``warmup = max_steps // 50``
  (``train.py:238-266``).
- grad clip ``max_grad_norm`` (``:339``) and **gradient accumulation** via
  ``optax.MultiSteps`` (``:335-360``).
- eval cadence: denser during the first epoch (``first_eval_steps=5`` →
  first eval after 1/5 of an epoch), then every 1/``eval_steps`` of an epoch
  (``train.py:37-38,236-238,366-386``).
- per-epoch checkpoint of the fusion params only — the LLM weights are never
  saved (``train.py:389-392``; LoRA adapters checkpoint separately, see
  ``deepdfa_tpu/llm/lora.py``).
- eval/test: threshold ``P(vul) > best_threshold``, classification report
  with macro avg for Big-Vul / weighted otherwise (``train.py:445-459,
  571-585``).

The whole step — LLM forward + fusion forward/backward/update — is ONE
compiled function; batches are static-shape (``TextBatch`` + ``GraphJoin``),
so it compiles once. For sharded LLMs pass ``llm_params`` already placed with
``mesh_shardings`` — GSPMD partitions the step; the fusion params are tiny and
stay replicated.
"""

from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Any, Callable, Iterable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deepdfa_tpu.data.prefetch import prefetch_to_device
from deepdfa_tpu.llm.dataset import GraphJoin, JoinedBatch, TextExamples, text_batches
from deepdfa_tpu.llm.fusion import FusionModel, fusion_loss
from deepdfa_tpu.train.metrics import classification_report

__all__ = [
    "JointConfig",
    "JointState",
    "weight_decay_mask",
    "cosine_warmup_schedule",
    "eval_points",
    "best_threshold_sweep",
    "make_joint_steps",
    "JointTrainer",
]


def best_threshold_sweep(
    probs: np.ndarray,
    labels: np.ndarray,
    *,
    macro: bool = True,
    grid: Iterable[float] | None = None,
) -> tuple[float, float]:
    """MSIVD's eval-time threshold selection: sweep ``grid`` (default
    0.01..0.99 in 0.01 steps) over F1 of the positive-probability vector
    and return ``(best_threshold, best_f1)``.

    Deterministic by construction: the grid is fixed, the comparison is
    strict, so ties keep the EARLIEST (lowest) threshold — the selected
    value is a pure function of ``(probs, labels, grid)``, which makes the
    cascade band (``serve.cascade.band_lo/hi``, usually straddling this
    threshold) reproducible across re-evaluations of the same checkpoint."""
    probs = np.asarray(probs, np.float64)
    labels = np.asarray(labels)
    ts = (np.round(np.arange(1, 100) / 100.0, 2) if grid is None
          else np.asarray(list(grid), np.float64))
    key = "f1_macro" if macro else "f1_weighted"
    best_t, best_f = float(ts[0]), -1.0
    for t in ts:
        f1 = classification_report(
            probs, labels, macro=macro, threshold=float(t))[key]
        if f1 > best_f:
            best_t, best_f = float(t), float(f1)
    return best_t, best_f


@dataclasses.dataclass(frozen=True)
class JointConfig:
    """Golden values = the reference argparse defaults (``train.py:588-801``)
    and module constants (``train.py:37-38``)."""

    block_size: int = 256
    train_batch_size: int = 4
    eval_batch_size: int = 4
    learning_rate: float = 5e-5
    weight_decay: float = 0.0
    adam_epsilon: float = 1e-8
    max_grad_norm: float = 1.0
    gradient_accumulation_steps: int = 1
    epochs: int = 1
    best_threshold: float = 0.5
    eval_steps: int = 2  # evals per epoch after the first
    first_eval_steps: int = 5  # evals per first epoch
    seed: int = 42
    # "bigvul" → macro avg (imbalanced); anything else → weighted avg
    dataset_style: str = "bigvul"
    use_gnn: bool = True  # False = --no_flowgnn presets
    # LineVul-combined mode (BASELINE config #3): fine-tune the encoder
    # end-to-end (CodeBERT is 125M params — trainable on one chip) while the
    # pretrained GGNN is frozen — the exact mirror of the MSIVD freeze
    # direction (frozen LLM, trained GNN). ``freeze_gnn`` zeroes updates to
    # the ``flowgnn_encoder`` subtree (``main_cli.py:136-145``'s
    # freeze_graph_weights).
    train_llm: bool = False
    # host→device prefetch depth for the join+transfer pipeline (the
    # DataLoader-worker analogue, data/prefetch.py); 0 disables. Default 1
    # (one staged + one in flight): joint graph batches can be dense
    # adjacencies — hundreds of MB each — so deeper queues trade real HBM
    # for overlap that one staged batch already buys
    prefetch: int = 1
    freeze_gnn: bool = False

    @property
    def report_avg(self) -> str:
        return "macro" if "bigvul" in self.dataset_style else "weighted"


class JointState(NamedTuple):
    params: Any  # fusion params (GGNN + head) — the ONLY trained tree
    opt_state: Any
    rng: jax.Array
    step: jnp.ndarray


def weight_decay_mask(params: Any) -> Any:
    """True = apply weight decay. The reference excludes ``bias`` and
    ``LayerNorm.weight`` (``train.py:242-260``); in our Flax trees that is any
    leaf named ``bias`` and any RMSNorm/LayerNorm ``weight``/``scale``."""

    def mask_path(path: tuple, _leaf) -> bool:
        keys = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
        if keys and keys[-1] in ("bias", "scale"):
            return False
        if keys and keys[-1] == "weight" and any("norm" in str(k).lower() for k in keys[:-1]):
            return False
        return True

    return jax.tree_util.tree_map_with_path(mask_path, params)


def cosine_warmup_schedule(lr: float, warmup_steps: int, total_steps: int):
    """HF ``get_cosine_schedule_with_warmup`` parity: linear 0→lr over
    ``warmup_steps``, cosine lr→0 over the rest."""
    warmup_steps = max(warmup_steps, 1)
    return optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=lr,
        warmup_steps=warmup_steps,
        # decay_steps includes warmup; the cosine segment must be non-empty
        decay_steps=max(total_steps, warmup_steps + 1),
        end_value=0.0,
    )


def gnn_freeze_labels(params: Any) -> Any:
    """"train"/"freeze" label pytree: every leaf under a ``flowgnn_encoder``
    scope is frozen (``freeze_graph_weights`` parity) — works on both the
    bare fusion tree and the combined ``{"fusion", "llm"}`` tree."""

    def lab(path: tuple, _leaf) -> str:
        keys = [str(getattr(p, "key", getattr(p, "name", p))) for p in path]
        return "freeze" if "flowgnn_encoder" in keys else "train"

    return jax.tree_util.tree_map_with_path(lab, params)


def joint_optimizer(cfg: JointConfig, steps_per_epoch: int, params: Any):
    """clip → AdamW(no-decay mask) → cosine-warmup, wrapped in MultiSteps for
    gradient accumulation (micro-step semantics identical to ``train.py``:
    update every ``gradient_accumulation_steps`` batches). With
    ``cfg.freeze_gnn`` the ``flowgnn_encoder`` subtree gets zero updates."""
    opt_steps = (cfg.epochs * steps_per_epoch) // cfg.gradient_accumulation_steps
    warmup = opt_steps // 50  # train.py:238 "args.warmup_steps = max_steps // 50"
    schedule = cosine_warmup_schedule(cfg.learning_rate, warmup, opt_steps)
    tx = optax.chain(
        optax.clip_by_global_norm(cfg.max_grad_norm),
        optax.adamw(
            schedule,
            eps=cfg.adam_epsilon,
            weight_decay=cfg.weight_decay,
            mask=weight_decay_mask(params),
        ),
    )
    if cfg.freeze_gnn:
        tx = optax.multi_transform(
            {"train": tx, "freeze": optax.set_to_zero()},
            gnn_freeze_labels(params),
        )
    if cfg.gradient_accumulation_steps > 1:
        tx = optax.MultiSteps(tx, cfg.gradient_accumulation_steps)
    return tx


def eval_points(steps_per_epoch: int, epoch: int, cfg: JointConfig) -> set[int]:
    """Step indices (within an epoch) after which to run eval. First epoch is
    denser (``first_eval_steps``), later epochs use ``eval_steps``
    (``train.py:236-238,366-386``)."""
    per = cfg.first_eval_steps if epoch == 0 else cfg.eval_steps
    stride = max(steps_per_epoch // per, 1)
    return {s for s in range(stride - 1, steps_per_epoch, stride)}


def make_joint_steps(
    llm: Any,  # any encoder module: (input_ids, pad_mask) -> hidden states
    fusion: FusionModel,
    tx: optax.GradientTransformation,
    train_llm: bool = False,
    on_stats: Callable[[Any], None] | None = None,
    freeze_gnn: bool = False,
) -> tuple[Callable, Callable]:
    """(train_step, eval_step), both jitted. ``llm_params`` is an input, not a
    capture, so sharded placements propagate and the tree is donated-free.
    The encoder is whatever maps ``(input_ids, pad_mask)`` to hidden states;
    counts it or the fusion model sow into a ``stats`` collection leave the
    jitted step as a fourth output and are handed, unread, to ``on_stats``
    after each launch.

    ``train_llm=False`` (MSIVD): the LLM forward runs on the constant
    ``llm_params`` input with no backward built through the stack.
    ``train_llm=True`` (LineVul-combined): the trained tree is
    ``{"fusion": ..., "llm": ...}`` and gradients flow through the encoder;
    the ``llm_params`` step argument is ignored (pass ``None``).

    ``freeze_gnn`` (``JointConfig.freeze_gnn``: ``tx`` zeroes the GGNN's
    updates): the ``flowgnn_encoder`` leaves are held out of the backward, so
    none is traced through the GGNN; their gradients are the zeros that
    ``tx`` would have made of them."""

    def hidden_states(llm_params, batch: JoinedBatch, dropout_rng=None):
        ids = jnp.asarray(batch.text.input_ids)
        # Explicit pad mask from the dataset (TextBatch.pad_mask): pads share
        # the eos id, so value-sniffing can't find them — the reference's
        # ``attention_mask = input_ids.ne(1)`` (model.py:50) masks *bos*
        # instead of pads; we carry the truth from tokenization time. RoPE is
        # relative, so arange positions over a left-padded row preserve all
        # real-token distances (a uniform shift); the RoBERTa encoder builds
        # mask-aware absolute positions itself.
        #
        # ``dropout_rng`` (train_llm steps only): a trained encoder runs
        # with its HF training regularisation — RobertaEncoder reads
        # hidden/attention dropout rates off its config; a frozen encoder is
        # never handed a key, matching the reference's frozen-LLM forward.
        kwargs = {} if dropout_rng is None else {"deterministic": False, "rngs": {"dropout": dropout_rng}}
        # an encoder may sow per-step counts into a ``stats`` collection
        # (a routed decoder's routing counts); most sow nothing: {}
        hidden, sown = llm.apply(
            {"params": llm_params}, ids, jnp.asarray(batch.text.pad_mask),
            mutable=["stats"], **kwargs,
        )
        return hidden, sown.get("stats", {})

    def loss_fn(params, llm_params, batch: JoinedBatch, rng):
        if train_llm:
            fusion_params, llm_params = params["fusion"], params["llm"]
        else:
            fusion_params = params
        if freeze_gnn and fusion.use_gnn:
            fusion_params = {**fusion_params, "flowgnn_encoder": jax.lax.stop_gradient(
                fusion_params["flowgnn_encoder"])}
        rng, enc_rng = jax.random.split(rng)
        hidden, stats = hidden_states(
            llm_params, batch, dropout_rng=enc_rng if train_llm else None
        )
        # the fusion model sows its own counts (the GGNN's view of the graph
        # budget) into the same collection; without a GGNN, nothing: {}
        logits, sown = fusion.apply(
            {"params": fusion_params},
            hidden,
            batch.graphs if fusion.use_gnn else None,
            deterministic=False,
            token_mask=jnp.asarray(batch.text.pad_mask),
            rngs={"dropout": rng},
            mutable=["stats"],
        )
        labels = jnp.asarray(batch.text.labels)
        mask = jnp.asarray(batch.mask)
        with jax.named_scope("loss"):
            loss, probs = fusion_loss(logits, labels, mask)
        return loss, (probs, {**stats, **sown.get("stats", {})})

    def train_step(state: JointState, llm_params, batch: JoinedBatch):
        rng, sub = jax.random.split(state.rng)
        (loss, (probs, stats)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, llm_params, batch, sub
        )
        # flax scopes the modules; clip + AdamW + apply_updates would lower
        # as bare jit(train_step)/mul — name them for the device trace
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
        return JointState(params, opt_state, rng, state.step + 1), loss, probs, stats

    jitted_train_step = jax.jit(train_step)

    def launch(state: JointState, llm_params, batch: JoinedBatch):
        """The step as its callers take it, ``(state, loss, probs)``; what the
        encoder sowed goes to ``on_stats`` as unread device arrays."""
        state, loss, probs, stats = jitted_train_step(state, llm_params, batch)
        if stats and on_stats is not None:
            on_stats(stats)
        return state, loss, probs

    @jax.jit
    def eval_step(params, llm_params, batch: JoinedBatch):
        if train_llm:
            fusion_params, llm_params = params["fusion"], params["llm"]
        else:
            fusion_params = params
        hidden, _ = hidden_states(llm_params, batch)
        logits = fusion.apply(
            {"params": fusion_params},
            hidden,
            batch.graphs if fusion.use_gnn else None,
            deterministic=True,
            token_mask=jnp.asarray(batch.text.pad_mask),
        )
        labels = jnp.asarray(batch.text.labels)
        mask = jnp.asarray(batch.mask)
        with jax.named_scope("loss"):
            loss, probs = fusion_loss(logits, labels, mask)
        return loss, probs

    return launch, eval_step


@dataclasses.dataclass
class JointTrainer:
    """The ``train``/``evaluate``/``test`` driver (``train.py:211-585``)."""

    llm: Any  # the encoder module: (input_ids, pad_mask) -> hidden states
    llm_params: Any
    fusion: FusionModel
    cfg: JointConfig
    join: GraphJoin | None  # None = no_flowgnn mode
    run_dir: Path | None = None
    # obs.TrainTelemetry the loop and its prefetch producer record into
    # (timing only); None = the process-wide one, obs.train_telemetry()
    telemetry: Any = None

    def __post_init__(self):
        self._steps: tuple[Callable, Callable] | None = None
        # what the encoder sowed in the step just launched (a routed
        # decoder's routing counts), as unread device arrays; most sow nothing
        self._launched: list = []
        self.num_missing = 0
        self.history: list[dict] = []
        if self.telemetry is None:
            from deepdfa_tpu.obs import train_telemetry

            self.telemetry = train_telemetry()

    @property
    def _llm_arg(self):
        """The frozen-encoder step argument: in ``train_llm`` mode the
        encoder lives inside ``state.params`` and the argument is unused —
        don't ship a second copy of the weights into every step."""
        return None if self.cfg.train_llm else self.llm_params

    def _joined(self, batch) -> JoinedBatch:
        if self.join is not None:
            return self.join.join(batch)
        return JoinedBatch(text=batch, graphs=None, mask=batch.mask)

    def _built(self, batches: Iterable) -> Iterable[JoinedBatch]:
        """The joined batches, as the prefetch producer pulls them: each pull
        runs inside the producer's ``batch.build`` span, whose counts of real
        and padded tokens are set here, from the numpy mask, before H2D."""
        tracer = self.telemetry.tracer
        for tb in batches:
            jb = self._joined(tb)
            build = tracer.current_span()
            if build is not None:
                build.attrs.update(
                    tokens_real=int(tb.pad_mask.sum()), tokens=tb.pad_mask.size)
            yield jb

    def _read_loss(self, pending: tuple, alone: bool) -> float:
        """The loss of the launched step ``pending``, read through
        ``float(loss)`` — where the loop waits for the device — inside that
        step's ``loss.sync`` span. ``alone`` says no later step had been
        launched at the read (an evaluation point, the epoch's end): the
        device then idles through the next launch. The read's return is the
        moment a step is known complete: the telemetry puts the interval
        since the last one on the span."""
        step, loss, wait_s, dispatch_s, stats = pending
        with self.telemetry.tracer.span(
            "loss.sync", step=step, reads=1, alone=int(alone)
        ) as sync:
            value = float(loss)
            self.telemetry.observe_read(sync, alone)
            if stats:
                # outputs of the step whose loss was just read: they are
                # there already, this is a copy and no second wait
                flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(stats))
                sync.attrs.update({
                    "_".join(str(getattr(k, "key", k)) for k in path): leaf.item()
                    for path, leaf in flat})
        self.telemetry.observe_step(wait_s, dispatch_s, sync.dur_s)
        return value

    def _build(
        self, steps_per_epoch: int, example: JoinedBatch, params: Any | None = None
    ) -> JointState | None:
        """Build the optimizer + jitted steps. With resumed ``params`` only
        the step machinery is built (no LLM forward / fusion init / optimizer
        state allocation — they'd be thrown away); without, a fresh
        :class:`JointState` is initialised and returned."""
        fresh = params is None
        rng = jax.random.key(self.cfg.seed)
        if fresh:
            rng, init_rng, drop_rng = jax.random.split(rng, 3)
            hidden = self.llm.apply(
                {"params": self.llm_params},
                jnp.asarray(example.text.input_ids),
                jnp.asarray(example.text.pad_mask),
            )
            params = self.fusion.init(
                {"params": init_rng, "dropout": drop_rng},
                hidden,
                example.graphs if self.fusion.use_gnn else None,
                deterministic=True,
                token_mask=jnp.asarray(example.text.pad_mask),
            )["params"]
            if self.cfg.train_llm:
                # LineVul-combined: the encoder joins the trained tree (and
                # its checkpoint — the reference saves fine-tuned CodeBERT)
                params = {"fusion": params, "llm": self.llm_params}
        self.tx = joint_optimizer(self.cfg, steps_per_epoch, params)
        self._steps = make_joint_steps(
            self.llm, self.fusion, self.tx, train_llm=self.cfg.train_llm,
            on_stats=self._launched.append, freeze_gnn=self.cfg.freeze_gnn,
        )
        if not fresh:
            return None
        return JointState(params, self.tx.init(params), rng, jnp.zeros((), jnp.int32))

    def train(
        self,
        train_examples: TextExamples,
        eval_examples: TextExamples,
        state: JointState | None = None,
    ) -> JointState:
        cfg = self.cfg
        telemetry, tracer = self.telemetry, self.telemetry.tracer
        n_batches = -(-len(train_examples) // cfg.train_batch_size)
        for epoch in range(cfg.epochs):
            telemetry.observe_epoch(epoch)
            batches = text_batches(
                train_examples,
                cfg.train_batch_size,
                shuffle=True,  # RandomSampler (train.py:227)
                seed=cfg.seed + epoch,
            )
            points = eval_points(n_batches, epoch, cfg)
            tr_loss = 0.0
            # the step in flight: (index, loss, its data.wait and
            # step.dispatch seconds, what its encoder sowed), launched and
            # not yet read
            pending = None
            with tracer.span("train.epoch", root=True, epoch=epoch):
                # overlap the host-side graph join + H2D transfer with the
                # running step (the index-join per batch is real host work —
                # the reference hides it in DataLoader workers)
                joined = prefetch_to_device(
                    self._built(batches), size=cfg.prefetch, tracer=tracer,
                    on_span=telemetry.observe_producer,
                )
                try:
                    # text_batches yields exactly n_batches
                    for step in range(n_batches):
                        with jax.profiler.StepTraceAnnotation(
                            "train", step_num=epoch * n_batches + step
                        ):
                            with tracer.span("data.wait", step=step) as wait:
                                jb = next(joined)
                            if self._steps is None or state is None:
                                built = self._build(
                                    n_batches, jb,
                                    params=None if state is None else state.params,
                                )
                                state = state if state is not None else built
                            train_step, _ = self._steps
                            with tracer.span("step.dispatch", step=step) as dispatch:
                                state, loss, _probs = train_step(
                                    state, self._llm_arg, jb
                                )
                            # one step stays in flight: this one is queued on
                            # the device before the loop waits for the loss of
                            # the one before it, so the host's launch runs
                            # beside the device instead of in front of it
                            if pending is not None:
                                tr_loss += self._read_loss(pending, alone=False)
                            stats = self._launched.pop() if self._launched else None
                            pending = (step, loss, wait.dur_s, dispatch.dur_s, stats)
                            if step in points:
                                tr_loss += self._read_loss(pending, alone=True)
                                pending = None
                                with tracer.span("eval", step=step):
                                    report = self.evaluate(state.params, eval_examples)
                                self.history.append(
                                    {"epoch": epoch, "step": step, **report}
                                )
                    # the epoch's last step, before its mean and checkpoint.
                    # Not in the ``finally``: after an exception the pending
                    # loss is dropped (a read there could block on, or
                    # re-raise from, a failed step and mask the first error)
                    if pending is not None:
                        tr_loss += self._read_loss(pending, alone=True)
                finally:
                    # the producer still holds its end-of-stream marker (or,
                    # after an exception, staged batches): stop and join it
                    joined.close()
                self.history.append(
                    {"epoch": epoch, "train_loss": tr_loss / max(n_batches, 1),
                     "telemetry": telemetry.epoch_stats()}
                )
                if self.run_dir is not None:
                    with tracer.span("checkpoint.save", checkpoint=f"epoch_{epoch}"):
                        self.save(state, f"epoch_{epoch}")
        if self.join is not None:
            self.num_missing = self.join.num_missing
        return state

    def _run_eval(
        self, params, examples: TextExamples
    ) -> tuple[float, np.ndarray, np.ndarray]:
        losses, probs_all, labels_all = [], [], []
        for tb in text_batches(examples, self.cfg.eval_batch_size):
            jb = self._joined(tb)
            if self._steps is None:  # standalone eval (test-only runs)
                self._build(1, jb, params=params)
            _, eval_step = self._steps
            loss, probs = eval_step(params, self._llm_arg, jb)
            losses.append(float(loss))
            keep = np.asarray(jb.mask)
            probs_all.append(np.asarray(probs)[keep])
            labels_all.append(np.asarray(tb.labels)[keep])
        return (
            float(np.mean(losses)) if losses else 0.0,
            np.concatenate(probs_all) if probs_all else np.zeros((0, 2)),
            np.concatenate(labels_all) if labels_all else np.zeros(0, np.int32),
        )

    def evaluate(self, params, examples: TextExamples) -> dict[str, float]:
        """``evaluate`` parity (``train.py:396-465``): mean loss + report."""
        loss, probs, labels = self._run_eval(params, examples)
        report = classification_report(
            probs[:, 1] if probs.size else probs.reshape(0),
            labels,
            macro=self.cfg.report_avg == "macro",
            threshold=self.cfg.best_threshold,
        )
        return {"eval_loss": loss, **{f"eval_{k}": v for k, v in report.items()}}

    def test(self, params, examples: TextExamples) -> dict[str, float]:
        """``test`` parity (``train.py:467-585``) minus profiling (that lives
        in ``deepdfa_tpu/train/profiling.py`` and wraps any step fn)."""
        loss, probs, labels = self._run_eval(params, examples)
        report = classification_report(
            probs[:, 1] if probs.size else probs.reshape(0),
            labels,
            macro=self.cfg.report_avg == "macro",
            threshold=self.cfg.best_threshold,
        )
        return {"test_loss": loss, **{f"test_{k}": v for k, v in report.items()}}

    def save(self, state: JointState, name: str) -> Path:
        """Fusion params only (``train.py:389-392`` saves ``gnn_model``'s
        state_dict; the frozen LLM is never written)."""
        import orbax.checkpoint as ocp

        path = (Path(self.run_dir) / name).absolute()
        ckptr = ocp.StandardCheckpointer()
        ckptr.save(path, state.params, force=True)
        ckptr.wait_until_finished()
        return path

    def load(self, template_params: Any, name: str) -> Any:
        import orbax.checkpoint as ocp

        path = (Path(self.run_dir) / name).absolute()
        return ocp.StandardCheckpointer().restore(path, template_params)
