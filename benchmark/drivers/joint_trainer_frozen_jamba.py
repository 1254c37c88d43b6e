"""Driver for ``JointTrainer.train`` with a frozen decoder that routes nothing
(``deepdfa_tpu/llm/jamba.py``: selective-scan layers with multi-query
attention every few, an MLP in every layer; ``JointConfig.train_llm=False``,
the MSIVD job).

The window, the wrapper pieces and the checked steps' readings over the
trained leaves are ``drivers/joint_trainer.py``'s; the frozen half is
``drivers/joint_trainer_frozen.py``'s (its ``load``, inherited) without its
``routing``: the trained tree is the fusion model's alone (GGNN + head), the
decoder's weights (made leaf by leaf on the device from the reference's lazy
mapping, stored at the program's dtype, ``A_log`` / ``D`` / ``dt_bias`` in
float32) are the step's ``llm_params`` argument, and the checked steps also
take the final-norm state of every real token, which the timed step does not
hand out.

**That state comes from a program of the check's own, built as the step is and
tied to it** (why: that file's docstring). ``_check`` is the step's loss
function once more — the very module, weights and batch through ``llm.apply``,
the fusion model, the loss, under ``value_and_grad`` over the trained tree —
with the states and the encoder's ``stats`` as further outputs and no
optimizer. On every checked step its ``probs`` are held against the timed
step's own (``tie.logits``), and the counts the step's encoder left on its
``loss.sync`` span (``ssm_layers``, ``ssm_fused``, ``attn_layers``,
``attn_fused``) against the same counts of ``_check``'s pass (``tie.counts``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deepdfa_tpu.config import GGNNConfig
from deepdfa_tpu.llm.fusion import FusionModel, fusion_loss
from deepdfa_tpu.llm.jamba import JambaConfig, JambaModel
from deepdfa_tpu.llm.joint import JointConfig, JointTrainer
from harness import spec
from harness.phases import Stop

_frozen = spec.load_module("drivers", "joint_trainer_frozen")  # ``load``, ``_trained``, ``free``
_base = _frozen._base
PUBLISHED = tuple(f.name for f in dataclasses.fields(JambaConfig))
COUNTS = ("ssm_", "attn_")  # the encoder's ``stats`` as ``loss.sync`` names them


def model_config(cfg: dict) -> JambaConfig:
    """The program's config from the file's published keys."""
    d = {k: cfg[k] for k in PUBLISHED if k in cfg}
    d["dtype"] = cfg["precision"]["compute_dtype"]
    return JambaConfig.from_hf_dict({**d, **cfg.get("program", {})})


def _flat_counts(stats: dict) -> dict:
    """``{'ssm_layers': 26, ...}`` of a ``stats`` collection, as
    ``JointTrainer._read_loss`` names what it copies onto the span."""
    return {f"{group}_{name}": int(value) for group, counts in stats.items()
            for name, value in counts.items()}


class Driver(_frozen.Driver):
    def __init__(self, cfg: dict, reference):
        self.cfg, self.reference = cfg, reference
        t, g = cfg["train"], cfg.get("gnn", {})
        self.llm_cfg = model_config(cfg)
        self.jcfg = JointConfig(
            block_size=t["block_size"], train_batch_size=t["train_batch_size"],
            eval_batch_size=t["train_batch_size"], learning_rate=t["learning_rate"],
            weight_decay=t["weight_decay"], adam_epsilon=t["adam_epsilon"],
            max_grad_norm=t["max_grad_norm"], epochs=t["epochs"], seed=t["shuffle_seed"],
            prefetch=t["prefetch"], use_gnn=cfg["use_gnn"], train_llm=False, freeze_gnn=False)
        fusion = FusionModel(
            gnn_cfg=GGNNConfig(
                hidden_dim=g.get("hidden_dim", 32), n_steps=g.get("n_steps", 5),
                concat_all_absdf=g.get("concat_all_absdf", True),
                layout=g.get("layout", "segment"), dtype=g.get("dtype", "float32")),
            input_dim=g.get("input_dim", 1002), llm_hidden_size=cfg["hidden_size"],
            use_gnn=cfg["use_gnn"], dropout_rate=cfg["head"]["dropout_rate"],
            pool=cfg["head"]["pool"])
        self.trainer = JointTrainer(
            llm=JambaModel(self.llm_cfg), llm_params=None, fusion=fusion,
            cfg=self.jcfg, join=None, run_dir=None)
        self.state = self.examples = self.data = None
        llm = self.trainer.llm

        def check_loss(params, llm_params, jb):
            """``make_joint_steps``' loss function (dropout is 0.0: no key is
            drawn from), the states and the encoder's counts beside ``probs``."""
            hidden, sown = llm.apply(
                {"params": llm_params}, jb.text.input_ids, jb.text.pad_mask, mutable=["stats"])
            logits = fusion.apply(
                {"params": params}, hidden, jb.graphs if fusion.use_gnn else None,
                deterministic=False, token_mask=jb.text.pad_mask,
                rngs={"dropout": jax.random.key(0)})
            loss, probs = fusion_loss(logits, jb.text.labels, jb.mask)
            return loss, (probs, hidden, sown["stats"])

        def check(params, llm_params, jb):
            (_, out), grads = jax.value_and_grad(check_loss, has_aux=True)(
                params, llm_params, jb)
            return out, optax.global_norm(grads)  # the backward pass stays in the program

        self._check = jax.jit(check)

    # -- set-up -----------------------------------------------------------
    def load(self, data: dict, weights, seed: int) -> None:
        """That driver's ``load`` (every decoder leaf stored at the program's
        dtype), then Mamba's three leaves again as they are made: float32."""
        super().load(data, weights, seed)
        for n in weights:
            if n.endswith(self.reference.FLOAT32_LEAVES):
                *path, leaf = n[len("llm/"):].split("/")
                functools.reduce(dict.__getitem__, path, self.trainer.llm_params)[leaf] = weights[n]

    # -- the run ----------------------------------------------------------
    def run(self, phases) -> dict:
        cfg, tr = self.cfg, self.trainer
        n_check = cfg["check"]["steps"]
        real_train, _ = self._real_steps
        self._run_t0 = time.time()  # the ring may hold an earlier run's spans
        losses: list[float] = []
        rows: list[np.ndarray] = []
        seen: list[tuple[str, jax.Array]] = []
        # per checked step: (hidden, the step's probs, mask, ``_check``'s probs and counts)
        checked: list[tuple] = []
        readings: dict = {}
        b1 = cfg["train"]["adam_b1"]
        named = lambda tree: {f"fusion/{k}": float(v) for k, v in _base.leaf_names(tree).items()}

        def train_step(state, llm_arg, jb):
            n = phases.step_begin()
            with phases.span("step.dispatch"):
                new_state, loss, probs = real_train(state, llm_arg, jb)
            seen.append((phases.phase, jb.text.indices))
            if n < n_check:
                rows.append(self._checked_rows(n, jb.text.indices))
                (check_probs, hidden, stats), _ = self._check(state.params, llm_arg, jb)
                checked.append((hidden, probs, jb.text.pad_mask, check_probs, stats))
            if n == 0:
                mu = jax.device_get(_base._norms(_base._adam_state(new_state.opt_state).mu))
                readings["grad1"] = {k: v / (1.0 - b1) for k, v in named(mu).items()}
            if n == n_check - 1:
                start = self._trained(self.reference.make_weights(cfg, self.seed))
                readings["delta"] = named(jax.device_get(
                    _base._diff_norms(new_state.params, start)))
            self.state = new_state
            return new_state, _base._Loss(loss, phases, losses), probs

        def eval_step(*_):
            raise RuntimeError(
                "JointTrainer.train reached an evaluation point inside the run: the "
                "traffic mix's epoch is too short for this speed")

        tr._steps = (train_step, eval_step)
        try:
            tr.train(self.examples, self.examples, state=self.state)
        except Stop:
            pass
        else:
            raise RuntimeError("the epoch ended before the phases did")
        finally:
            tr._steps = self._real_steps
        jax.block_until_ready(self.state)
        self._join_producers()
        if tr.join is not None and tr.join.num_missing:
            raise RuntimeError(f"{tr.join.num_missing} examples found no graph")

        readings["loss"] = losses[:n_check]
        readings["hidden"], readings["logits"] = [], []
        tie = readings["tie"] = {"logits": [], "counts": [], "step_counts": []}
        for n, (hidden, probs, mask, check_probs, stats) in enumerate(checked):
            readings["hidden"].append(np.asarray(hidden.astype(jnp.float32))[np.asarray(mask)])
            readings["logits"].append(np.log(np.asarray(probs, np.float64)))
            tie["logits"].append(np.log(np.asarray(check_probs, np.float64)))
            tie["counts"].append(_flat_counts(jax.device_get(stats)))
            tie["step_counts"].append(self._span_counts(n))
        del checked
        window_losses = [v for (p, _), v in zip(seen, losses) if p == "window"]
        counters = self._count([i for p, i in seen if p == "window"])
        return {
            "readings": readings,
            "follow": {"step_rows": rows, "total_steps": self.total_steps},
            "counters": counters,
            "attempted": counters["steps"],
            "failed": sum(1 for v in window_losses if not math.isfinite(v)),
        }

    def _span_counts(self, step: int) -> dict:
        """What the timed step's own encoder counted in checked step ``step``:
        this run's ``loss.sync`` span of that step (one epoch: a span's
        ``step`` is the call's index)."""
        (span,) = [s for s in self.trainer.telemetry.tracer.spans()
                   if s.name == "loss.sync" and s.start_s >= self._run_t0
                   and s.attrs.get("step") == step]
        return {k: v for k, v in span.attrs.items() if k.startswith(COUNTS)}
