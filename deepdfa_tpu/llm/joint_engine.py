"""Tier-2 scoring engine: the joint LLM+GNN model, packaged for serving.

``llm/joint.py`` trains the MSIVD fusion head (frozen LLM hidden states +
GGNN embedding) and checkpoints the fusion params per epoch; this module is
the *serving* half — restore the newest ``epoch_N`` fusion checkpoint from a
``train_joint.py`` run dir and rescore borderline functions through the fused
head. The cascade (``serve/cascade.py``) escalates tier-1 borderline scores
here; ``JointEngine.score`` is the whole contract:

- input: ``[(source_text, Graph), ...]`` — the request's raw source (the LLM
  branch tokenizes it) paired with the already-encoded CPG graph (the GGNN
  branch; ``None`` with ``use_gnn=False``);
- output: ``P(vulnerable)`` per item, computed by the *same jitted
  ``eval_step``* the trainer evaluates with (``make_joint_steps``), so a
  restored checkpoint scores bit-identically to its training-eval pass;
- static shapes: every chunk pads to ``max_batch`` text rows and a fixed
  ``(max_nodes, max_edges)`` graph budget, so the step compiles once.

Two construction paths, through ``llm/families.py`` like ``train_joint.py``:

- :meth:`from_run_dir` **hermetic** (default): ``tiny_llama`` +
  :class:`HashTokenizer` — no downloaded weights, the tests/smoke path;
- :meth:`from_run_dir` **sharded**: pass ``hf_checkpoint=`` (+ ``mesh=``) to
  load CodeLlama through ``llm/convert.py`` and tp/fsdp placement
  (``mesh_shardings``); the fusion tree is tiny and stays replicated.
"""

from __future__ import annotations

import threading
from pathlib import Path
from typing import Any, Sequence

import numpy as np

__all__ = ["JointEngine", "newest_epoch_dir"]


def _placeholder_graph(n_nodes: int = 1):
    """A minimal graph carrying the full feature schema real extractions emit
    (`_ABS_DATAFLOW` combined-vocab + one column per subkey) — enough to trace
    fusion.init / warm the compiled program under any ``concat_all_absdf``
    setting."""
    from deepdfa_tpu.config import ALL_SUBKEYS
    from deepdfa_tpu.data.graphs import Graph

    feats = {f"_ABS_DATAFLOW_{sk}": np.zeros(n_nodes, np.int32) for sk in ALL_SUBKEYS}
    feats["_ABS_DATAFLOW"] = np.zeros(n_nodes, np.int32)
    return Graph(
        senders=np.zeros(0, np.int32),
        receivers=np.zeros(0, np.int32),
        node_feats=feats,
        gid=0,
    )


def newest_epoch_dir(run_dir: str | Path) -> Path | None:
    """Newest ``epoch_N`` checkpoint under a ``train_joint.py`` run dir
    (numeric sort — ``epoch_10`` beats ``epoch_9``), or None."""
    epochs = sorted(
        Path(run_dir).glob("epoch_*"),
        key=lambda p: int(p.name.split("_")[1]),
    )
    return epochs[-1] if epochs else None


class JointEngine:
    """Joint-model rescorer over a restored fusion checkpoint.

    Thread-safe: the cascade dispatcher is a single thread, but scans may
    share an engine across workers — ``score`` serialises on one lock (the
    jitted forward is the whole cost; contention is not the bottleneck).
    """

    def __init__(
        self,
        llm,
        llm_params,
        fusion,
        fusion_params,
        tokenizer,
        jcfg,
        *,
        max_batch: int = 4,
        max_nodes: int = 4096,
        max_edges: int = 8192,
    ):
        from deepdfa_tpu.llm.joint import make_joint_steps
        from deepdfa_tpu.serve.engine import params_content_hash

        self.llm = llm
        self.llm_params = llm_params
        self.fusion = fusion
        self.fusion_params = fusion_params
        self.tokenizer = tokenizer
        self.cfg = jcfg
        self.max_batch = int(max_batch)
        self.max_nodes = int(max_nodes)
        self.max_edges = int(max_edges)
        # same rev scheme as tier 1 (ScoringEngine): content hash of the
        # trained tree — the drift sentinel and /metrics key on it
        self.model_rev = params_content_hash(fusion_params)
        # the trainer's own jitted eval_step — restore→rescore parity is
        # definitional, not best-effort (tx is train-step-only; None is safe)
        _, self._eval_step = make_joint_steps(llm, fusion, None, train_llm=False)
        self._lock = threading.Lock()

    # ------------------------------------------------------------------ build

    @classmethod
    def from_run_dir(
        cls,
        run_dir: str | Path,
        *,
        jcfg=None,
        gnn_cfg=None,
        input_dim: int | None = None,
        vocab_size: int = 2048,
        use_gnn: bool = True,
        max_batch: int = 4,
        max_nodes: int = 4096,
        max_edges: int = 8192,
        hf_checkpoint: str | None = None,
        mesh=None,
    ) -> "JointEngine":
        """Restore the newest ``epoch_N`` fusion checkpoint from a
        ``train_joint.py`` run dir.

        The encoder is ``families.build_encoder``'s, as ``train_joint.py``
        builds it (``llama`` is all tier 2 serves today): by default the
        hermetic ``tiny_llama`` at ``vocab_size`` + :class:`HashTokenizer`;
        ``hf_checkpoint`` switches to CodeLlama, placed over ``mesh`` if given.
        """
        import orbax.checkpoint as ocp

        from deepdfa_tpu.config import FeatureConfig, GGNNConfig
        from deepdfa_tpu.llm.families import FAMILIES, build_encoder
        from deepdfa_tpu.llm.fusion import FusionModel
        from deepdfa_tpu.llm.joint import JointConfig

        jcfg = jcfg or JointConfig()
        family = FAMILIES["llama"]
        # a checkpoint brings its own config; else the hermetic one
        llm_cfg = None if hf_checkpoint else family.hermetic(jcfg.block_size, vocab_size)
        llm, llm_params, tokenizer, llm_cfg = build_encoder(family, llm_cfg, jcfg.block_size, hf_checkpoint, mesh)

        fusion = FusionModel(
            gnn_cfg=gnn_cfg or GGNNConfig(),
            input_dim=input_dim if input_dim is not None else FeatureConfig().input_dim,
            llm_hidden_size=llm_cfg.hidden_size,
            use_gnn=use_gnn,
            dropout_rate=0.1,
            pool=family.pool,
        )

        newest = newest_epoch_dir(run_dir)
        if newest is None:
            raise FileNotFoundError(
                f"no epoch_* fusion checkpoint under {run_dir} — run "
                "scripts/train_joint.py --do_train first"
            )
        template = cls._template_params(llm, llm_params, fusion, jcfg, max_nodes, max_edges)
        fusion_params = ocp.StandardCheckpointer().restore(
            newest.absolute(), template
        )
        return cls(
            llm, llm_params, fusion, fusion_params, tokenizer, jcfg,
            max_batch=max_batch, max_nodes=max_nodes, max_edges=max_edges,
        )

    @staticmethod
    def _template_params(llm, llm_params, fusion, jcfg, max_nodes, max_edges):
        """A fusion param tree of the right shape for the orbax restore —
        traced from one placeholder batch (the ``_restore_newest_epoch``
        idiom in ``scripts/train_joint.py``)."""
        import jax
        import jax.numpy as jnp

        from deepdfa_tpu.data.graphs import batch_np

        ids = np.zeros((1, jcfg.block_size), np.int32)
        pad_mask = np.ones((1, jcfg.block_size), bool)
        hidden = llm.apply({"params": llm_params}, jnp.asarray(ids),
                           jnp.asarray(pad_mask))
        graphs = None
        if fusion.use_gnn:
            graphs = batch_np([_placeholder_graph()], 2, max_nodes, max_edges)
        init_rng, drop_rng = jax.random.split(jax.random.key(0))
        return fusion.init(
            {"params": init_rng, "dropout": drop_rng},
            hidden,
            graphs,
            deterministic=True,
            token_mask=jnp.asarray(pad_mask),
        )["params"]

    # ------------------------------------------------------------------ score

    def score(self, items: Sequence[tuple[str, Any]]) -> np.ndarray:
        """``P(vulnerable)`` per ``(source_text, graph)`` item, chunked to
        ``max_batch`` so the jitted step never re-specialises."""
        out = np.zeros(len(items), np.float64)
        with self._lock:
            for start in range(0, len(items), self.max_batch):
                chunk = items[start : start + self.max_batch]
                out[start : start + len(chunk)] = self._score_chunk(chunk)
        return out

    def _score_chunk(self, chunk: Sequence[tuple[str, Any]]) -> np.ndarray:
        from deepdfa_tpu.llm.dataset import (
            GraphJoin,
            JoinedBatch,
            encode_functions,
            text_batches,
        )

        n = len(chunk)
        examples = encode_functions(
            [text for text, _ in chunk],
            [0] * n,  # labels are loss-only; score reads probs
            self.tokenizer,
            self.cfg.block_size,
        )
        tb = next(text_batches(examples, self.max_batch))
        if self.fusion.use_gnn:
            join = GraphJoin(
                graphs={i: g for i, (_, g) in enumerate(chunk) if g is not None},
                max_nodes=self.max_nodes,
                max_edges=self.max_edges,
            )
            jb = join.join(tb)
        else:
            jb = JoinedBatch(text=tb, graphs=None, mask=tb.mask)
        _loss, probs = self._eval_step(self.fusion_params, self.llm_params, jb)
        return np.asarray(probs)[:n, 1].astype(np.float64)

    # ----------------------------------------------------------------- warmup

    def warmup(self) -> dict:
        """Compile the one (max_batch, block, graph-budget) program before
        traffic — a cascade must not pay XLA compile on its first borderline
        request."""
        g = _placeholder_graph() if self.fusion.use_gnn else None
        self.score([("int main() { return 0; }", g)])
        return {"max_batch": self.max_batch, "model_rev": self.model_rev}

    def describe(self) -> dict:
        return {
            "model_rev": self.model_rev,
            "max_batch": self.max_batch,
            "block_size": self.cfg.block_size,
            "use_gnn": bool(self.fusion.use_gnn),
        }
