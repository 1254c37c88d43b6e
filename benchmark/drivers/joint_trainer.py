"""Driver for the entry point ``deepdfa_tpu.llm.joint.JointTrainer.train``.

The window drives ``JointTrainer.train`` itself — its ``text_batches`` →
``GraphJoin.join`` → ``prefetch_to_device`` → jitted ``train_step`` →
``float(loss)`` loop, unmodified — and ends it from outside: the trainer's
``_steps`` pair is replaced by a wrapper that calls the real jitted step and
raises :class:`harness.phases.Stop` at a step boundary once the phases are
over. ``JointTrainer.train`` has no step-boundary stop of its own (the GGNN
``Trainer.train_epoch`` has ``preemption``); PERF.md lists that hook.

The same wrapper takes the readings ``correct`` is decided from, of the first
steps of the very state and compiled step the window then goes on with: each
step's loss as the loop itself reads it, Adam's first moment after one step
(``mu = (1 - b1) * gradient``, the gradient as the optimizer got it), and the
parameters' change after the last checked step.

Only this file knows the program's types. From the benchmark it takes plain
arrays (traffic), a flat ``{'a/b/c': array}`` dict of weights, and the phases.
"""

from __future__ import annotations

import gc
import math
import threading

import jax
import jax.numpy as jnp
import numpy as np
from flax.traverse_util import unflatten_dict

from deepdfa_tpu.config import GGNNConfig
from deepdfa_tpu.data.graphs import Graph
from deepdfa_tpu.llm.dataset import GraphJoin, TextExamples
from deepdfa_tpu.llm.fusion import FusionModel
from deepdfa_tpu.llm.joint import JointConfig, JointState, JointTrainer
from deepdfa_tpu.llm.roberta import RobertaConfig, RobertaEncoder
from harness.phases import Stop


def leaf_names(tree) -> dict:
    """``{'a/b/c': leaf}`` of a tree of dicts (and optax's empty masked nodes)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(k, "key", k)) for k in path): leaf for path, leaf in flat}


@jax.jit
def _norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


@jax.jit
def _diff_norms(a, b):
    return jax.tree.map(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b)


def _adam_state(opt_state):
    has = lambda x: hasattr(x, "mu") and hasattr(x, "nu")
    found = [s for s in jax.tree_util.tree_leaves(opt_state, is_leaf=has) if has(s)]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state in the optimizer's, found {len(found)}")
    return found[0]


class _Loss:
    """The step's loss as the loop gets it. The loop's own ``float(loss)`` is
    the per-step host sync; this times it, keeps the value, and marks the
    start of the loop's wait for its next batch."""

    def __init__(self, value, phases, sink):
        self._value, self._phases, self._sink = value, phases, sink

    def __float__(self) -> float:
        with self._phases.span("loss.sync"):
            out = float(self._value)
        self._sink.append(out)
        self._phases.wait_begin()
        return out

    def __jax_array__(self):
        return self._value

    def __getattr__(self, name):
        return getattr(self._value, name)


class Driver:
    def __init__(self, cfg: dict, reference):
        self.cfg, self.reference = cfg, reference
        m, t = cfg["model"], cfg["train"]
        llm_cfg = RobertaConfig.from_hf_dict({**m, "dtype": cfg["precision"]["compute_dtype"]})
        g = cfg.get("gnn", {})
        self.jcfg = JointConfig(
            block_size=t["block_size"], train_batch_size=t["train_batch_size"],
            eval_batch_size=t["train_batch_size"], learning_rate=t["learning_rate"],
            weight_decay=t["weight_decay"], adam_epsilon=t["adam_epsilon"],
            max_grad_norm=t["max_grad_norm"], epochs=t["epochs"], seed=t["shuffle_seed"],
            prefetch=t["prefetch"], use_gnn=cfg["use_gnn"], train_llm=True,
            freeze_gnn=cfg["freeze_gnn"])
        fusion = FusionModel(
            gnn_cfg=GGNNConfig(
                hidden_dim=g.get("hidden_dim", 32), n_steps=g.get("n_steps", 5),
                concat_all_absdf=g.get("concat_all_absdf", True),
                layout=g.get("layout", "segment"), dtype=g.get("dtype", "float32")),
            input_dim=g.get("input_dim", 1002), llm_hidden_size=m["hidden_size"],
            use_gnn=cfg["use_gnn"], dropout_rate=cfg["head"]["dropout_rate"],
            pool=cfg["head"]["pool"])
        self.trainer = JointTrainer(
            llm=RobertaEncoder(llm_cfg), llm_params=None, fusion=fusion,
            cfg=self.jcfg, join=None, run_dir=None)
        self.state = self.examples = self.data = None

    # -- set-up -----------------------------------------------------------
    def load(self, data: dict, weights: dict, seed: int) -> None:
        """Hand the seed's inputs and weights to the program, in its types."""
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
        params = unflatten_dict(weights, sep="/")  # the nested dicts the program's trees are
        tr.llm_params = params["llm"]
        self.steps_per_epoch = -(-len(self.examples) // self.jcfg.train_batch_size)
        if tr._steps is None:  # resumed-params build: optimizer + jitted steps only
            tr._build(self.steps_per_epoch, None, params=params)
            self._real_steps = tr._steps
            self._opt_init = jax.jit(tr.tx.init)
        self.state = JointState(
            params, self._opt_init(params), jax.random.key(self.jcfg.seed),
            jnp.zeros((), jnp.int32))

    @property
    def total_steps(self) -> int:
        return self.jcfg.epochs * self.steps_per_epoch

    @property
    def setup_steps(self) -> int:
        """Calls of the step that count as set-up: the checked, then the warm."""
        return self.cfg["check"]["steps"] + self.cfg["check"]["warm_steps"]

    # -- the run ----------------------------------------------------------
    def run(self, phases) -> dict:
        """Drive ``JointTrainer.train`` through the phases. Returns the
        readings of the checked steps (``readings``), what the reference needs
        to follow them (``follow``: its ``run``'s further arguments), the
        window's exact counts of work (``counters``) and the window's steps
        ``attempted`` and ``failed`` (a loss that is not finite)."""
        cfg, tr = self.cfg, self.trainer
        n_check = cfg["check"]["steps"]
        real_train, _ = self._real_steps
        losses: list[float] = []
        rows: list[np.ndarray] = []
        seen: list[tuple[str, jax.Array]] = []  # (phase, the batch's row indices)
        readings: dict = {}
        b1 = cfg["train"]["adam_b1"]

        def train_step(state, llm_arg, jb):
            n = phases.step_begin()
            with phases.span("step.dispatch"):
                new_state, loss, probs = real_train(state, llm_arg, jb)
            seen.append((phases.phase, jb.text.indices))
            if n < n_check:
                rows.append(self._checked_rows(n, jb.text.indices))
            if n == 0:
                mu = jax.device_get(_norms(_adam_state(new_state.opt_state).mu))
                readings["grad1"] = {k: float(v) / (1.0 - b1) for k, v in leaf_names(mu).items()}
            if n == n_check - 1:
                start = unflatten_dict(self.reference.make_weights(cfg, self.seed), sep="/")
                delta = jax.device_get(_diff_norms(new_state.params, start))
                del start
                readings["delta"] = {k: float(v) for k, v in leaf_names(delta).items()}
            self.state = new_state
            return new_state, _Loss(loss, phases, losses), probs

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
        window_losses = [v for (p, _), v in zip(seen, losses) if p == "window"]
        counters = self._count([i for p, i in seen if p == "window"])
        return {
            "readings": readings,
            "follow": {"step_rows": rows, "total_steps": self.total_steps},
            "counters": counters,
            "attempted": counters["steps"],
            "failed": sum(1 for v in window_losses if not math.isfinite(v)),
        }

    def _checked_rows(self, n: int, indices) -> np.ndarray:
        """The rows of checked step ``n``, held to what the configuration's
        ``check.labels`` states of them."""
        rows = np.asarray(indices).astype(np.int64)
        want = self.cfg["check"].get("labels")
        if want == "all_negative" and self.data["labels"][rows[rows >= 0]].any():
            raise RuntimeError(
                f"checked step {n + 1} holds a vulnerable function, and the configuration "
                "states check.labels = all_negative: with k of a batch's rows vulnerable "
                "the rows' gradients can cancel in the batch mean, and the comparison then "
                "reads that mean's conditioning, not the program (PERF.md section 2). The "
                "program's shuffle, the configuration's shuffle_seed or the mix's "
                "label_seed / n_examples changed: take a label_seed under which the "
                "checked batches are of one label again (benchmark/tests/test_traffic.py "
                "has the rule)")
        if want not in (None, "all_negative"):
            raise ValueError(f"unknown check.labels {want!r}")
        return rows

    def _count(self, index_arrays: list) -> dict:
        """Exact counts of the work in the given steps, from the traffic's own
        arrays by the rows the program fed."""
        if not index_arrays:
            return {"steps": 0}
        idx = np.concatenate([np.asarray(a) for a in jax.device_get(index_arrays)])
        real = idx[idx >= 0]
        lengths = self.data["lengths"][real]
        out = {
            "steps": len(index_arrays),
            "functions": int(real.size),
            "tokens_real": int(lengths.sum()),
            "tokens_sq": int((lengths * lengths).sum()),
            "tokens_padded": int(idx.size * self.jcfg.block_size),
        }
        if self.cfg["use_gnn"]:
            gr = self.data["graphs"]
            out["graph_nodes_real"] = int(gr["n_nodes"][real].sum())
            out["graph_edges_real"] = int(np.diff(gr["edge_off"])[real].sum())
            out["graph_nodes_padded"] = len(index_arrays) * self.cfg["graph_join"]["max_nodes"]
        return out

    @staticmethod
    def _join_producers() -> None:
        """``prefetch_to_device`` joins its thread when its generator closes;
        the abandoned generator closes on collection."""
        gc.collect()
        for t in threading.enumerate():
            if t.name == "prefetch_to_device":
                t.join(timeout=10.0)
                if t.is_alive():
                    raise RuntimeError("prefetch_to_device producer did not stop")

    def free(self) -> None:
        """Drop the program's device state before the reference runs."""
        self.state = None
        self.trainer.llm_params = None
        gc.collect()
