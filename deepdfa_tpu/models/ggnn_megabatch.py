"""GGNN with the whole-model fused Pallas forward (``layout=megabatch``).

Same model as :class:`deepdfa_tpu.models.ggnn.GGNN` over the same
segment-layout :class:`BatchedGraphs`, with an identical parameter tree
(every container reproduces ``nn.Dense``/``nn.Embed`` leaves under the
same scopes with the same initialisers, so fresh inits are bit-identical
and checkpoints interchange across all four layouts) — but the ENTIRE
forward (embed → messages → GRU → attention pool → label head) runs as ONE
Pallas launch (:func:`deepdfa_tpu.ops.megabatch.fused_ggnn_model`). The
fused layout already removed the per-round dispatches; this removes the
pooling and head dispatches too, which is what megabatch packing needs:
one launch per packed megabatch instead of a ladder of per-bucket ones.

Routing is static per bucket shape: if the megabatch VMEM plan
(:func:`fits_vmem_megabatch`) refuses the shape, ``__call__`` computes via
:func:`megabatch_reference` — plain XLA segment ops, operation-for-
operation the segment layout's math, so the fallback is bit-identical to
the segment twin on the same params (pinned by ``tests/test_megabatch.py``).

The whole-model kernel hard-codes the flagship configuration: concat-
subkey abstract-dataflow embeddings (embed width == hidden width), sum
aggregation, graph-level labels, classifier head. The excluded variants
(``dataflow_families``, union aggregators, ``label_style="node"``,
``encoder_mode``) raise at construction — use ``layout=segment`` (or
``fused``) for those; silently diverging would be worse.
"""

from __future__ import annotations

import logging

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepdfa_tpu.config import ALL_SUBKEYS
from deepdfa_tpu.data.graphs import BatchedGraphs
from deepdfa_tpu.models.ggnn import GGNN
from deepdfa_tpu.models.ggnn_fused import GatedGraphConvFused, _DenseParams
from deepdfa_tpu.ops.megabatch import (
    MegabatchPlan,
    fused_ggnn_model,
    megabatch_reference,
)

logger = logging.getLogger("deepdfa_tpu")

__all__ = ["GGNNMegabatch"]


class _PoolingParams(nn.Module):
    """``GlobalAttentionPooling``'s parameter tree (the ``gate`` Dense)
    without the apply logic — the whole-model kernel consumes the raw
    arrays. Same scope path + init fns keep fresh inits bit-identical."""

    in_features: int

    def setup(self):
        self.gate = _DenseParams(self.in_features, 1)


class GGNNMegabatch(GGNN):
    """:class:`GGNN` computed in one whole-model Pallas launch
    (``model.layout=megabatch``), with bit-identical segment-twin routing
    for shapes the VMEM plan refuses."""

    def setup(self):
        cfg = self.cfg
        if not cfg.concat_all_absdf or cfg.dataflow_families or cfg.interproc_families:
            raise ValueError(
                "layout=megabatch supports the concat-subkey abstract-"
                "dataflow config only (concat_all_absdf=True, "
                "dataflow_families=False, interproc_families=False) — the "
                "whole-model kernel's embed prologue hard-codes the "
                "stacked-table gather; use layout=segment/fused for other "
                "embedding configs"
            )
        if cfg.label_style != "graph" or cfg.encoder_mode:
            raise ValueError(
                "layout=megabatch supports graph-level classification only "
                "(label_style='graph', encoder_mode=False) — the fused "
                "epilogue IS the pooling+head; use layout=segment otherwise"
            )
        if cfg.aggregation != "sum":
            raise ValueError(
                f"layout=megabatch supports aggregation='sum' only; got "
                f"{cfg.aggregation!r} — use layout=segment for the "
                "union-lattice aggregators"
            )
        self.compute_dtype = jnp.dtype(cfg.dtype)
        self.embeddings = {
            sk: nn.Embed(
                self.input_dim,
                cfg.hidden_dim,
                dtype=self.compute_dtype,
                name=f"embed_{sk}",
            )
            for sk in ALL_SUBKEYS
        }
        hidden_dim = cfg.hidden_dim * len(ALL_SUBKEYS)
        self.ggnn = GatedGraphConvFused(
            out_feats=hidden_dim,
            n_steps=cfg.n_steps,
            aggregation=cfg.aggregation,
            dtype=self.compute_dtype,
            bwd_kernel=getattr(cfg, "bwd_kernel", "auto"),
        )
        out_in = 2 * hidden_dim
        self.pooling = _PoolingParams(out_in)
        self.head = [
            _DenseParams(
                out_in,
                1 if i == cfg.num_output_layers - 1 else out_in,
                name=f"out_{i}",
            )
            for i in range(cfg.num_output_layers)
        ]

    def plan_for(self, max_nodes: int, max_edges: int,
                 max_graphs: int) -> MegabatchPlan:
        """The static VMEM plan for a bucket shape (what routing consults)."""
        return MegabatchPlan(
            max_graphs=max_graphs,
            max_nodes=max_nodes,
            max_edges=max_edges,
            width=self.cfg.hidden_dim * len(ALL_SUBKEYS),
            n_steps=self.cfg.n_steps,
            table_rows=self.input_dim * len(ALL_SUBKEYS),
            embed_width=self.cfg.hidden_dim,
            n_head_layers=self.cfg.num_output_layers,
        )

    def __call__(self, batch: BatchedGraphs, taps: tuple | None = None) -> jnp.ndarray:
        if taps is not None:
            raise ValueError(
                "per-step taps are a segment-layout diagnostic — the whole-"
                "model kernel does not materialise per-round states (use "
                "layout=segment for tap-based gradient probes)"
            )
        cfg = self.cfg
        ct = self.compute_dtype
        table = jnp.concatenate(
            [self.embeddings[sk].embedding for sk in ALL_SUBKEYS], axis=0
        ).astype(ct)
        ids = jnp.stack(
            [
                batch.node_feats[f"_ABS_DATAFLOW_{sk}"] + i * self.input_dim
                for i, sk in enumerate(ALL_SUBKEYS)
            ],
            axis=-1,
        )
        conv = self.ggnn
        ew, eb = conv.edge_linear.kernel, conv.edge_linear.bias
        xw, xb = conv.gru.x_proj.kernel, conv.gru.x_proj.bias
        hw, hb = conv.gru.h_proj.kernel, conv.gru.h_proj.bias
        gw, gb = self.pooling.gate.kernel, self.pooling.gate.bias
        head = tuple((layer.kernel, layer.bias) for layer in self.head)
        plan = self.plan_for(batch.max_nodes, batch.senders.shape[0],
                             batch.max_graphs)
        if plan.fits:
            interpret = jax.default_backend() != "tpu"
            return fused_ggnn_model(
                table, ids, batch.senders, batch.receivers,
                batch.node_gidx, batch.node_mask,
                ew, eb, xw, xb, hw, hb, gw, gb, head,
                n_steps=cfg.n_steps, n_graphs=batch.max_graphs,
                interpret=interpret, edges_sorted=True,
            )
        # over-plan: bit-identical segment-twin math, same params. Said out
        # loud, once per traced shape — the Trainer counts the steps it
        # routes itself, a direct model.apply has only this line
        logger.warning(
            "layout=megabatch: shape (%d nodes, %d edges, %d graphs) is "
            "over the whole-model kernel's plan (%.1f MiB working set) — "
            "computing through the segment-twin math",
            batch.max_nodes, batch.senders.shape[0], batch.max_graphs,
            plan.working_set / 2**20)
        return megabatch_reference(
            table, ids, batch.senders, batch.receivers,
            batch.node_gidx, batch.node_mask,
            ew, eb, xw, xb, hw, hb, gw, gb, head,
            n_steps=cfg.n_steps, n_graphs=batch.max_graphs,
            edges_sorted=True,
        )
