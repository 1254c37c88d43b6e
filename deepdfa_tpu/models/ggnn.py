"""Gated Graph Neural Network over batched CFGs, in Flax.

Re-implements the semantics of the reference's ``FlowGNNGGNNModule``
(``DDFA/code_gnn/models/flow_gnn/ggnn.py:22-109``), which stacked DGL's
``GatedGraphConv`` (C++/CUDA SpMM kernels) and ``GlobalAttentionPooling`` —
here everything is XLA: embeddings and the GRU/linear matmuls hit the MXU,
message passing is gather + ``segment_sum``, attention pooling is a masked
segment softmax. Shapes are static (padded batches), so the whole forward
jits once per bucket.

Exact parity notes (validated by ``tests/test_ggnn_parity.py`` against a
torch scatter-add reference implementation of the DGL ops):

- ``GatedGraphConv`` applies a per-edge-type Linear (with bias) to the
  **source** state, sums incoming messages, then a GRU cell update; input
  features are zero-padded from ``in_feats`` to ``out_feats``. With
  ``n_etypes=1`` the per-edge Linear commutes to a per-node Linear before the
  gather (identical math, one matmul instead of |E|).
- ``GlobalAttentionPooling(gate_nn=Linear(d,1))``: softmax of the gate over
  nodes *within each graph*, then weighted sum of node states.
- Per-subkey embedding tables are concatenated when ``concat_all_absdf``
  (``ggnn.py:47-54``): embed and hidden widths each ×4.
- The classifier input is ``concat([ggnn_out, feat_embed])``
  (``ggnn.py:98``); ``encoder_mode`` returns the pooled embedding for LLM
  fusion (``ggnn.py:104-107``).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepdfa_tpu.config import (
    ALL_SUBKEYS,
    DFA_FEATURE_DIMS,
    GGNNConfig,
    active_dfa_families,
)
from deepdfa_tpu.data.graphs import BatchedGraphs
from deepdfa_tpu.ops.segment import gather, segment_softmax, segment_sum

__all__ = ["GGNN", "GRUCell"]


class GRUCell(nn.Module):
    """GRU cell with torch ``nn.GRUCell`` gate layout (reset/update/new), the
    update rule DGL's GatedGraphConv uses. ``features`` is the hidden width.

    The three per-gate projections of each input are fused into ONE
    ``(features → 3·features)`` matmul per input (columns ordered ``r|z|n`` —
    exactly torch's ``weight_ih``/``weight_hh`` row layout, transposed), so a
    step costs 2 MXU-shaped matmuls instead of 6 slivers. Per-output-element
    math is unchanged: fusing along the output axis does not reorder any
    reduction."""

    features: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray, h: jnp.ndarray) -> jnp.ndarray:
        xp = nn.Dense(3 * self.features, dtype=self.dtype, name="x_proj")(x)
        hp = nn.Dense(3 * self.features, dtype=self.dtype, name="h_proj")(h)
        xr, xz, xn = jnp.split(xp, 3, axis=-1)
        hr, hz, hn = jnp.split(hp, 3, axis=-1)
        r = nn.sigmoid(xr + hr)
        z = nn.sigmoid(xz + hz)
        n = jnp.tanh(xn + r * hn)
        return (1.0 - z) * n + z * h


class GatedGraphConv(nn.Module):
    """n_steps of (linear → gather(senders) → aggregate(receivers) → GRU).

    Self-loop edges are expected in the data (added at materialisation time,
    parity with ``dbize_graphs.py:26``).

    ``aggregation``: ``"sum"`` (DGL ``GatedGraphConv`` parity) or the
    differentiable set unions ``"union_simple"``/``"union_relu"`` — the
    "learn the DFA lattice" aggregators (``clipper.py:50-77``; mailbox fold
    replaced by closed-form segment ops, ``ops/union.py``). Union
    aggregation treats messages as soft membership bits, matching the
    reaching-definitions meet operator ∪.

    ``edges_sorted``: whether edges arrive sorted by receiver. True is the
    ``batch_np`` contract (every batch in this framework) and lets each
    scatter-add take XLA's sorted-segment fast path. Callers feeding
    hand-built edge lists that are NOT receiver-sorted MUST pass False —
    a false promise makes TPU segment reductions silently wrong.
    """

    out_feats: int
    n_steps: int
    aggregation: str = "sum"
    edges_sorted: bool = True
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(
        self, h: jnp.ndarray, senders: jnp.ndarray, receivers: jnp.ndarray,
        taps: tuple | None = None,
    ) -> jnp.ndarray:
        """``taps`` (diagnostics only): a tuple of ``n_steps`` zero arrays
        shaped like ``h`` added to the state after each GRU update — the
        standard trick for reading per-step gradients dL/dh_t through the
        unrolled chain (grad w.r.t. taps[t]); None (the default) changes
        nothing."""
        n_nodes = h.shape[0]
        # A false edges_sorted promise makes TPU segment reductions silently
        # wrong; when running eagerly (tests, hand-built batches — concrete
        # arrays, not tracers) verify it. Jitted callers (Trainer) pass
        # batch_np output, whose contract is host-side receiver sort.
        if self.edges_sorted and not isinstance(receivers, jax.core.Tracer):
            r = np.asarray(receivers)
            if r.size and np.any(np.diff(r) < 0):
                raise ValueError(
                    "edges_sorted=True but receivers are not sorted by "
                    "receiver — pass edges_sorted=False for hand-built edge "
                    "lists, or sort them (batch_np does this on the host)"
                )
        if h.shape[-1] > self.out_feats:
            raise ValueError("in_feats must be <= out_feats (DGL contract)")
        if h.shape[-1] < self.out_feats:
            pad = jnp.zeros((n_nodes, self.out_feats - h.shape[-1]), h.dtype)
            h = jnp.concatenate([h, pad], axis=-1)
        edge_linear = nn.Dense(self.out_feats, dtype=self.dtype, name="edge_linear")
        gru = GRUCell(self.out_feats, dtype=self.dtype, name="gru")
        if self.aggregation not in ("sum", "union_simple", "union_relu"):
            raise ValueError(f"unknown aggregation {self.aggregation!r}")
        if self.aggregation != "sum":
            from deepdfa_tpu.ops.union import segment_union_relu, segment_union_simple

            union = (
                segment_union_simple
                if self.aggregation == "union_simple"
                else segment_union_relu
            )
        # Edges arrive sorted by receiver — the ``batch_np`` contract (see
        # ``BatchedGraphs``) — so every scatter-add in the unrolled chain runs
        # XLA's sorted-segment fast path with NO device-side argsort: the
        # O(E log² E) bitonic sort this used to pay per jitted forward now
        # happens once per batch as a numpy argsort on the host.
        # Python loop, unrolled by trace: n_steps is small (5) and static;
        # unrolling lets XLA pipeline the matmuls instead of a lax.scan barrier.
        # Each round (message, scatter, GRU) is named for the device trace.
        for _step in range(self.n_steps):
            with jax.named_scope(f"round_{_step}"):
                msg_src = edge_linear(h)
                if self.aggregation == "sum":
                    agg = segment_sum(gather(msg_src, senders), receivers, n_nodes,
                                      indices_are_sorted=self.edges_sorted)
                else:
                    # union space is [0,1] soft membership: messages AND the
                    # node's own state map through sigmoid (the reference fold
                    # starts from ``nodes.data["h"]``, clipper.py:70-73, with h
                    # living in bit space in its experiments; sigmoid keeps the
                    # union algebra valid for our unconstrained GRU state and
                    # matches exactly at saturation)
                    msgs = nn.sigmoid(msg_src)
                    agg = union(nn.sigmoid(h), msgs, senders, receivers,
                                indices_are_sorted=self.edges_sorted)
                h = gru(agg, h)
                if taps is not None:
                    h = h + taps[_step]
        return h


class GlobalAttentionPooling(nn.Module):
    """Masked segment-softmax attention readout (DGL ``GlobalAttentionPooling``
    with ``gate_nn=Linear(d, 1)`` and no feat_nn, parity ``ggnn.py:66-68``)."""

    dtype: Any = jnp.float32

    @nn.compact
    def __call__(
        self,
        h: jnp.ndarray,
        node_gidx: jnp.ndarray,
        node_mask: jnp.ndarray,
        num_graphs: int,
    ) -> jnp.ndarray:
        gate_logit = nn.Dense(1, dtype=self.dtype, name="gate")(h)[:, 0]
        # node_gidx is non-decreasing by construction (batch_np concatenates
        # graphs in order), so every readout scatter takes the sorted fast path
        gate = segment_softmax(gate_logit, node_gidx, num_graphs, mask=node_mask,
                               indices_are_sorted=True)
        # statement saliency for `predict`: which nodes the readout weighted.
        # sow is a no-op unless the caller applies with
        # mutable=["intermediates"] — training/inference paths are unchanged.
        self.sow("intermediates", "gate_weights", gate)
        return segment_sum(gate[:, None] * h, node_gidx, num_graphs,
                           indices_are_sorted=True)


class GGNN(nn.Module):
    """The flagship DeepDFA model: abstract-dataflow embeddings → GGNN →
    attention pooling → MLP classifier (or pooled embedding in encoder mode).
    """

    cfg: GGNNConfig
    input_dim: int

    def setup(self):
        cfg = self.cfg
        self.compute_dtype = jnp.dtype(cfg.dtype)
        embed_dim = cfg.hidden_dim
        if cfg.concat_all_absdf:
            self.embeddings = {
                sk: nn.Embed(
                    self.input_dim,
                    embed_dim,
                    dtype=self.compute_dtype,
                    name=f"embed_{sk}",
                )
                for sk in ALL_SUBKEYS
            }
            embed_dim *= len(ALL_SUBKEYS)
            hidden_dim = cfg.hidden_dim * len(ALL_SUBKEYS)
        else:
            self.embedding = nn.Embed(
                self.input_dim, embed_dim, dtype=self.compute_dtype, name="embed"
            )
            hidden_dim = cfg.hidden_dim
        fams = active_dfa_families(cfg.dataflow_families, cfg.interproc_families)
        if fams:
            # static-analysis families (liveness/uninit/taint, plus the
            # interprocedural ireach/itaint): small closed value sets, one
            # hidden_dim-wide table each, concatenated after the subkey
            # embeddings (widths from config.DFA_FEATURE_DIMS)
            self.dfa_embeddings = {
                fam: nn.Embed(
                    DFA_FEATURE_DIMS[fam],
                    cfg.hidden_dim,
                    dtype=self.compute_dtype,
                    name=f"embed_dfa_{fam}",
                )
                for fam in fams
            }
            embed_dim += cfg.hidden_dim * len(fams)
            hidden_dim += cfg.hidden_dim * len(fams)
        # factory hook: GGNNFused swaps in the Pallas VMEM-resident conv
        # under the same "ggnn" scope, keeping the parameter tree identical
        self.ggnn = self._conv(hidden_dim)
        out_in = embed_dim + hidden_dim
        if cfg.label_style == "graph":
            self.pooling = GlobalAttentionPooling(dtype=self.compute_dtype)
        if not cfg.encoder_mode:
            self.head = [
                nn.Dense(
                    1 if i == cfg.num_output_layers - 1 else out_in,
                    dtype=self.compute_dtype,
                    name=f"out_{i}",
                )
                for i in range(cfg.num_output_layers)
            ]

    def _conv(self, hidden_dim: int) -> nn.Module:
        """Build the message-passing conv (overridden by ``GGNNFused``)."""
        return GatedGraphConv(
            out_feats=hidden_dim,
            n_steps=self.cfg.n_steps,
            aggregation=self.cfg.aggregation,
            dtype=self.compute_dtype,
        )

    def _embed_dfa(self, batch: BatchedGraphs) -> jnp.ndarray:
        # same fused-gather trick as the subkey tables: the family tables
        # differ in row count but share the hidden width, so they stack along
        # axis 0 with cumulative row offsets into the ids.
        fams = active_dfa_families(self.cfg.dataflow_families,
                                   self.cfg.interproc_families)
        table = jnp.concatenate(
            [self.dfa_embeddings[fam].embedding for fam in fams], axis=0
        ).astype(self.compute_dtype)
        ids_cols = []
        offset = 0
        for fam in fams:
            ids_cols.append(batch.node_feats[f"_DFA_{fam}"] + offset)
            offset += DFA_FEATURE_DIMS[fam]
        ids = jnp.stack(ids_cols, axis=-1)
        out = jnp.take(table, ids, axis=0)
        return out.reshape(*ids.shape[:-1], -1)

    def embed_nodes(self, batch: BatchedGraphs) -> jnp.ndarray:
        if self.cfg.concat_all_absdf:
            # One fused gather instead of 4: stack the per-subkey tables into
            # a (4·input_dim, embed) matrix (params-only concat — XLA hoists
            # it out of the step), offset each subkey's ids into its table
            # slice, gather once, and flatten (n, 4, embed) -> (n, 4·embed).
            # Row-major reshape preserves exactly the per-subkey concat order.
            table = jnp.concatenate(
                [self.embeddings[sk].embedding for sk in ALL_SUBKEYS], axis=0
            ).astype(self.compute_dtype)
            ids = jnp.stack(
                [
                    batch.node_feats[f"_ABS_DATAFLOW_{sk}"] + i * self.input_dim
                    for i, sk in enumerate(ALL_SUBKEYS)
                ],
                axis=-1,
            )
            out = jnp.take(table, ids, axis=0)
            out = out.reshape(*ids.shape[:-1], -1)
        else:
            out = self.embedding(batch.node_feats["_ABS_DATAFLOW"])
        if self.cfg.dataflow_families or self.cfg.interproc_families:
            out = jnp.concatenate([out, self._embed_dfa(batch)], axis=-1)
        return out

    def __call__(self, batch: BatchedGraphs, taps: tuple | None = None) -> jnp.ndarray:
        cfg = self.cfg
        feat_embed = self.embed_nodes(batch)
        ggnn_out = self.ggnn(feat_embed, batch.senders, batch.receivers, taps=taps)
        out = jnp.concatenate([ggnn_out, feat_embed], axis=-1)
        if cfg.label_style == "graph":
            out = self.pooling(
                out, batch.node_gidx, batch.node_mask, batch.max_graphs
            )
        if cfg.encoder_mode:
            return out
        for i, layer in enumerate(self.head):
            out = layer(out)
            if i != len(self.head) - 1:
                out = nn.relu(out)
        return out[..., 0].astype(jnp.float32)
