"""Driver for ``JointTrainer.train`` with the decoder frozen
(``JointConfig.train_llm=False``, the MSIVD job): a latent-attention
routed-expert decoder's final hidden states, pooled at the last real token,
joined with the *trained* GGNN's graph embedding and classified.

The window is driven as ``drivers/joint_trainer.py`` drives it — the
trainer's own loop, ended from outside at a step boundary — and this driver
reuses that file's wrapper pieces. What differs: the trained tree is the
fusion model's alone (GGNN + head), the decoder's weights (bfloat16, made leaf
by leaf on the device from the reference's lazy mapping) are the step's
``llm_params`` argument, and the checked steps also take **what reads the
decoder directly**: the final-norm state of every real token and every
layer's routing choices, which the timed step does not hand out.

**They come from a program of the check's own, built as the step is and tied
to it.** ``_check`` is the step's loss function once more — the very module,
weights and batch through ``llm.apply``, the fusion model, the loss, under
``value_and_grad`` over the trained tree — with the states and the choices as
further outputs and no optimizer. On every checked step its ``probs`` are held
against the timed step's own (``tie.logits``), and the routing counts the step
left on its ``loss.sync`` span against the same counts of ``_check``'s choices
(``tie.counts``). Why the whole loss function and not the decoder alone: XLA
lowers the decoder inside the step's program and the decoder as a program of
its own a little differently — a bfloat16 ulp at some 200 of the state's 50M
elements, 6-50 real tokens a step — and where one of them is a pooled token's
the logits part by up to 9e-4 (12 of 185 steps); built as the step is,
``_check`` gave the step's very bits on each of those steps (PERF.md section
2). A decoder that differs in the step alone parts the two. The window's
routing counts come from the same spans.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax.traverse_util import unflatten_dict

from deepdfa_tpu.config import GGNNConfig
from deepdfa_tpu.data.graphs import Graph
from deepdfa_tpu.llm.dataset import GraphJoin, TextExamples
from deepdfa_tpu.llm.fusion import FusionModel, fusion_loss
from deepdfa_tpu.llm.joint import JointConfig, JointState, JointTrainer
from deepdfa_tpu.llm.longcat import LongcatConfig, LongcatModel
from harness import spec
from harness.phases import Stop

_base = spec.load_module("drivers", "joint_trainer")
PUBLISHED = tuple(f.name for f in dataclasses.fields(LongcatConfig))


def model_config(cfg: dict) -> LongcatConfig:
    """The program's config from the file's published keys: the router keeps
    its published width, ``experts_held`` says which experts are here."""
    d = {k: cfg[k] for k in PUBLISHED if k in cfg}
    d["n_routed_experts"] = cfg["published"]["n_routed_experts"]
    d["dtype"] = cfg["precision"]["compute_dtype"]
    return LongcatConfig.from_hf_dict({**d, **cfg.get("program", {})})


class Driver(_base.Driver):
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
            llm=LongcatModel(self.llm_cfg), llm_params=None, fusion=fusion,
            cfg=self.jcfg, join=None, run_dir=None)
        self.state = self.examples = self.data = None
        llm = self.trainer.llm

        def check_loss(params, llm_params, jb):
            """``make_joint_steps``' loss function (dropout is 0.0: no key is
            drawn from), the states and the routing choices beside ``probs``."""
            hidden, sown = llm.apply(
                {"params": llm_params}, jb.text.input_ids, jb.text.pad_mask,
                mutable=["stats", "routing"])
            logits = fusion.apply(
                {"params": params}, hidden, jb.graphs if fusion.use_gnn else None,
                deterministic=False, token_mask=jb.text.pad_mask,
                rngs={"dropout": jax.random.key(0)})
            loss, probs = fusion_loss(logits, jb.text.labels, jb.mask)
            return loss, (probs, hidden, sown["routing"])

        def check(params, llm_params, jb):
            (_, out), grads = jax.value_and_grad(check_loss, has_aux=True)(
                params, llm_params, jb)
            return out, optax.global_norm(grads)  # the backward pass stays in the program

        self._check = jax.jit(check)

    # -- set-up -----------------------------------------------------------
    def load(self, data: dict, weights, seed: int) -> None:
        """``weights`` is the reference's lazy mapping: each decoder leaf is
        made on the device, stored at the program's dtype and dropped as
        float32 before the next is made."""
        self.data, self.seed = data, seed
        self.examples = TextExamples(
            input_ids=data["input_ids"], labels=data["labels"],
            indices=data["indices"], pad_mask=data["pad_mask"])
        tr = self.trainer
        if self.cfg["use_gnn"]:
            gr = data["graphs"]
            no, eo = gr["node_off"], gr["edge_off"]
            graphs = {
                i: Graph(
                    senders=gr["senders"][eo[i]:eo[i + 1]],
                    receivers=gr["receivers"][eo[i]:eo[i + 1]],
                    node_feats={k: v[no[i]:no[i + 1]] for k, v in gr["node_feats"].items()},
                    gid=i)
                for i in range(len(no) - 1)}
            tr.join = GraphJoin(graphs=graphs, **self.cfg["graph_join"])
        dtype = jnp.dtype(self.llm_cfg.dtype)
        router = lambda n: n.endswith(("router_kernel", "router_bias"))  # float32 as published
        tr.llm_params = unflatten_dict(
            {n[len("llm/"):]: weights[n] if router(n) else weights[n].astype(dtype)
             for n in weights if n.startswith("llm/")}, sep="/")
        params = self._trained(weights)
        self.steps_per_epoch = -(-len(self.examples) // self.jcfg.train_batch_size)
        if tr._steps is None:
            tr._build(self.steps_per_epoch, None, params=params)
            self._real_steps = tr._steps
            self._opt_init = jax.jit(tr.tx.init)
        self.state = JointState(
            params, self._opt_init(params), jax.random.key(self.jcfg.seed),
            jnp.zeros((), jnp.int32))

    @staticmethod
    def _trained(weights) -> dict:
        return unflatten_dict(
            {n[len("fusion/"):]: weights[n] for n in weights if n.startswith("fusion/")}, sep="/")

    # -- the run ----------------------------------------------------------
    def run(self, phases) -> dict:
        cfg, tr = self.cfg, self.trainer
        n_check = cfg["check"]["steps"]
        real_train, _ = self._real_steps
        self._run_t0 = time.time()  # the ring may hold an earlier run's spans
        losses: list[float] = []
        rows: list[np.ndarray] = []
        seen: list[tuple[str, jax.Array]] = []
        # per checked step: (hidden, routing, the step's probs, mask, ``_check``'s probs)
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
                (check_probs, hidden, routing), _ = self._check(state.params, llm_arg, jb)
                checked.append((hidden, routing, probs, jb.text.pad_mask, check_probs))
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
        readings["hidden"], readings["routing"], readings["logits"] = [], [], []
        layers = [f"layers_{i}" for i in range(self.llm_cfg.num_layers)]
        tie = readings["tie"] = {"logits": [], "counts": [], "step_counts": []}
        for n, (hidden, routing, probs, mask, check_probs) in enumerate(checked):
            mask = np.asarray(mask)
            readings["hidden"].append(np.asarray(hidden.astype(jnp.float32))[mask])
            readings["routing"].append(np.stack(
                [np.asarray(routing[name]["moe"]["choice"][0]) for name in layers]))
            readings["logits"].append(np.log(np.asarray(probs, np.float64)))
            tie["logits"].append(np.log(np.asarray(check_probs, np.float64)))
            tie["counts"].append(self._counts_of(readings["routing"][-1]))
            tie["step_counts"].append(self._span_counts(n))
        del checked
        window_losses = [v for (p, _), v in zip(seen, losses) if p == "window"]
        counters = self._count([i for p, i in seen if p == "window"])
        counters.update(self._routing_counts(
            [k for k, (p, _) in enumerate(seen) if p == "window"], counters["steps"]))
        return {
            "readings": readings,
            "follow": {"step_rows": rows, "total_steps": self.total_steps,
                       "routing": readings["routing"]},
            "counters": counters,
            "attempted": counters["steps"],
            "failed": sum(1 for v in window_losses if not math.isfinite(v)),
        }

    def _counts_of(self, choice: np.ndarray) -> dict:
        """The routing counts of one step as the encoder's ``stats`` sum them
        over layers, from every layer's choices [layers, b, s, k] (-1: a pad)."""
        lo, hi = self.llm_cfg.held
        held = (choice >= lo) & (choice < hi)
        zero = choice >= self.llm_cfg.n_routed_experts
        load = (choice[..., None] == np.arange(lo, hi)).sum(axis=(1, 2, 3))  # [layers, held]
        return {"moe_assigned": int((choice >= 0).sum()), "moe_held": int(held.sum()),
                "moe_zero": int(zero.sum()),
                "moe_absent": int(((choice >= 0) & ~held & ~zero).sum()),
                "moe_load_max": int(load.max(axis=1).sum()), "moe_dropped": 0}

    def _loss_syncs(self, steps: set[int]) -> list:
        """This run's ``loss.sync`` spans of the given steps (one epoch: a
        span's ``step`` is the call's index) that carry routing counts."""
        return [s for s in self.trainer.telemetry.tracer.spans()
                if s.name == "loss.sync" and s.start_s >= self._run_t0
                and s.attrs.get("step") in steps and "moe_held" in s.attrs]

    def _span_counts(self, step: int) -> dict:
        """What the timed step's own encoder counted in checked step ``step``."""
        (span,) = self._loss_syncs({step})
        return {k: v for k, v in span.attrs.items() if k.startswith("moe_")}

    def _routing_counts(self, window_steps: list[int], n_steps: int) -> dict:
        """The window's assignments to held experts, from the program's own
        ``loss.sync`` spans. The step still in flight at the stop is never
        read: its count is the mean of the others'."""
        held = [s.attrs["moe_held"] for s in self._loss_syncs(set(window_steps))]
        if not held or not n_steps:
            return {}
        return {"moe_held_assignments": int(round(sum(held) * n_steps / len(held)))}

    def free(self) -> None:
        self.state = None
        self.trainer.llm_params = None
        gc.collect()
