"""LLM ⊕ GGNN fusion heads — the trainable part of joint training.

Flax re-design of ``MSIVD/msivd/model.py``:

- :class:`ClassificationHead` — ``model.py:11-29``: take the first-token
  state (the ``<s>``/[CLS] slot), concat the pooled graph embedding, then
  ``dropout → dense(hidden) → tanh → dropout → out_proj(2)``.
- :class:`FusionModel` — the ``GNNModel`` wrapper (``model.py:62-89``): runs
  the GGNN in ``encoder_mode`` over the joined graph batch and classifies the
  concatenation. Returns 2-way logits; loss/softmax live in
  :func:`fusion_loss` so the same forward serves train and inference.
- The frozen-LLM forward (``LLMModel.forward``, ``model.py:42-59``) is *not* a
  module here: the joint step calls ``LlamaModel`` directly (its final-norm
  hidden states are exactly ``hidden_states[-1]``) with no gradient flowing —
  see ``deepdfa_tpu/llm/joint.py``.

TPU notes: every example owns graph slot *i* of the batch
(``GraphJoin.join``), so aligning graph embeddings with examples is a static
slice, not a gather. Masked examples (padding / missing graph) still flow
through the forward — masking happens in the loss, keeping shapes static.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax.numpy as jnp
import optax

from deepdfa_tpu.config import GGNNConfig
from deepdfa_tpu.data.graphs import BatchedGraphs, compact_view, view_fits
from deepdfa_tpu.data.dense import DenseBatch
from deepdfa_tpu.llm.layers import sow_stats

__all__ = ["ClassificationHead", "FusionModel", "fusion_loss"]

# The compact views of a segment-layout graph batch that the GGNN may run
# over in place of the whole budget, as divisors of the budget the batch
# arrives with (its shapes), smallest view first. ``GraphJoin`` has one static
# budget, sized for the worst batch; a batch holds 2.4% of it in the mean, and
# an eighth holds 99.9% of the batches of 16 that Big-Vul's sizes make.
VIEW_DIVISORS = (8,)
# The smallest budget, in nodes, that gets views. What a view costs is
# tracing: the encoder once more in every program that holds it (on the v5e's
# host ~0.75 s of start-up), and three times more where a backward goes
# through the choice. What it saves is 0.2 ms of forward a step for every
# thousand nodes left out, 0.45 ms with the backward. Under this size that is
# less of a step than a view costs the start; a 32nd of the budget as a
# second view is the same trade (0.8 ms of a 56 ms step for those 0.75 s).
MIN_VIEW_BUDGET = 32768


def pool_tokens(
    features: jnp.ndarray, token_mask: jnp.ndarray | None, pool: str
) -> jnp.ndarray:
    """Select the per-example summary token from ``[b, s, h]`` hidden states.

    ``pool="last"`` (default): the last *real* token — under a causal LM this
    is the only position that has attended to the whole function, and with
    the framework's left-padding it is simply position ``s-1``; ``token_mask``
    generalises to right padding. This replaces the reference's
    ``features[:, 0, :]`` "CLS" read (``model.py:21``) — under a *causal*
    decoder position 0 attends only to itself, so that slot is a constant
    vector for every input (a CodeBERT-ism that defeats the LLM branch);
    ``pool="first"`` keeps it available for strict parity comparisons.

    ``pool="cls"``: the first *real* token — the right read for
    bidirectional encoders (CodeBERT/LineVul, config #3), where ``<s>`` IS a
    summary of the whole sequence; mask-aware so the framework's left-pad
    convention works (with right padding or no pads it equals "first")."""
    if pool == "first":
        return features[:, 0, :]
    if pool == "cls":
        if token_mask is None:
            return features[:, 0, :]
        first = jnp.argmax(token_mask.astype(jnp.int32), axis=1)
        return jnp.take_along_axis(features, first[:, None, None], axis=1)[:, 0, :]
    if pool != "last":
        raise ValueError(f"unknown pool {pool!r}")
    if token_mask is None:
        return features[:, -1, :]
    s = features.shape[1]
    # index of last True per row; all-False rows fall back to s-1 (masked out
    # of the loss anyway).
    rev = jnp.flip(token_mask.astype(jnp.int32), axis=1)
    last = s - 1 - jnp.argmax(rev, axis=1)
    return jnp.take_along_axis(features, last[:, None, None], axis=1)[:, 0, :]


class ClassificationHead(nn.Module):
    """``model.py:11-29`` in Flax. ``dropout_rate`` mirrors the LLM config's
    ``attention_dropout`` (the reference reuses it for the head)."""

    hidden_size: int
    dropout_rate: float = 0.0
    pool: str = "last"  # "last" (corrected) | "first" (reference parity)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(
        self,
        features: jnp.ndarray,  # [b, s, h] LLM final hidden states
        flowgnn_embed: jnp.ndarray | None,  # [b, d] or None (no_flowgnn mode)
        deterministic: bool = True,
        token_mask: jnp.ndarray | None = None,  # [b, s] True = real token
    ) -> jnp.ndarray:
        x = pool_tokens(features, token_mask, self.pool)
        if flowgnn_embed is not None:
            x = jnp.concatenate([x, flowgnn_embed.astype(x.dtype)], axis=-1)
        x = nn.Dropout(self.dropout_rate, deterministic=deterministic)(x)
        x = nn.Dense(self.hidden_size, dtype=self.dtype, name="dense")(x)
        x = jnp.tanh(x)
        x = nn.Dropout(self.dropout_rate, deterministic=deterministic)(x)
        return nn.Dense(2, dtype=self.dtype, name="out_proj")(x).astype(jnp.float32)


class FusionModel(nn.Module):
    """GGNN encoder + classification head (``GNNModel``, ``model.py:62-89``).

    ``gnn_cfg`` is forced into encoder mode; pass ``use_gnn=False`` for the
    reference's ``--no_flowgnn`` presets (LLM-only head)."""

    gnn_cfg: GGNNConfig
    input_dim: int
    llm_hidden_size: int
    use_gnn: bool = True
    dropout_rate: float = 0.0
    pool: str = "last"
    dtype: Any = jnp.float32

    def setup(self):
        if self.use_gnn:
            import dataclasses

            from deepdfa_tpu.models import make_model

            cfg = dataclasses.replace(self.gnn_cfg, encoder_mode=True, label_style="graph")
            # layout-aware (cfg.layout segment|dense): both forwards share
            # one parameter tree, so the joint checkpoint is layout-portable
            self.flowgnn_encoder = make_model(cfg, self.input_dim)
        self.classifier = ClassificationHead(
            hidden_size=self.llm_hidden_size,
            dropout_rate=self.dropout_rate,
            pool=self.pool,
            dtype=self.dtype,
        )

    def _encode_compact(self, graphs: BatchedGraphs) -> jnp.ndarray:
        """The encoder's pooled rows ``[max_graphs, out_dim]`` over the
        smallest view of ``graphs`` that holds the batch's real nodes and
        edges (:func:`compact_view`), the whole budget where none does or
        the budget is under ``MIN_VIEW_BUDGET``: chosen on the device from
        the batch's own masks, inside the one compiled step. Every branch is the same module over the same
        parameters (flax's lifted ``switch``; ``init`` runs the whole budget
        alone) and slices its own operands. What ran is sown into
        ``stats`` for whoever applies with ``mutable=["stats"]`` (the joint
        step: onto ``loss.sync``)."""
        if self.is_initializing():
            # the leaves ``init`` always drew: a lifted branch would fold its
            # own name into the parameters' keys
            return self.flowgnn_encoder(graphs)
        rungs = [(graphs.max_nodes // d, graphs.senders.shape[0] // d) for d in VIEW_DIVISORS
                 if graphs.max_nodes >= MIN_VIEW_BUDGET]
        # views nest, so the ones too small come first: their count is the
        # index of the smallest that fits, or of the whole budget after them
        index = jnp.asarray(
            sum((~view_fits(graphs, n, e)).astype(jnp.int32) for n, e in rungs), jnp.int32)
        branches = [
            lambda mdl, g, n=n, e=e: mdl.flowgnn_encoder(compact_view(g, n, e))
            for n, e in rungs
        ] + [lambda mdl, g: mdl.flowgnn_encoder(g)]
        # ``variables="params"``: the encoder's node-length ``intermediates``
        # (``gate_weights``) differ in shape by branch and stay inside
        if rungs:
            pooled = nn.switch(index, branches, self, graphs, variables="params", rngs=False)
        else:
            pooled = self.flowgnn_encoder(graphs)
        sizes = jnp.asarray([n for n, _ in rungs] + [graphs.max_nodes], jnp.int32)
        sow_stats(self, "ggnn", {"nodes_real": graphs.node_mask.sum(dtype=jnp.int32),
                                 "nodes_computed": sizes[index],
                                 "compact": (index < len(rungs)).astype(jnp.int32)})
        return pooled

    def __call__(
        self,
        llm_hidden_states: jnp.ndarray,  # [b, s, h]
        graphs: BatchedGraphs | DenseBatch | None,  # layout per gnn_cfg.layout
        deterministic: bool = True,
        token_mask: jnp.ndarray | None = None,  # [b, s] True = real token
    ) -> jnp.ndarray:
        embed = None
        if self.use_gnn:
            # Fail with a nameable error instead of an opaque jit shape
            # mismatch when the GraphJoin was built for the other layout
            # (round-3 advisor finding): the batch TYPE is the layout.
            is_dense_batch = isinstance(graphs, DenseBatch)
            want_dense = self.gnn_cfg.layout == "dense"
            if is_dense_batch != want_dense:
                raise TypeError(
                    f"FusionModel(layout={self.gnn_cfg.layout!r}) got a "
                    f"{'dense' if is_dense_batch else 'segment'}-layout graph "
                    "batch — construct GraphJoin with the same layout as "
                    "fusion.gnn_cfg.layout"
                )
            if self.gnn_cfg.layout == "segment":
                pooled = self._encode_compact(graphs)  # [max_graphs, out_dim]
            else:
                pooled = self.flowgnn_encoder(graphs)
            b = llm_hidden_states.shape[0]
            embed = pooled[:b]  # slot i belongs to example i (GraphJoin contract)
        return self.classifier(
            llm_hidden_states, embed, deterministic=deterministic, token_mask=token_mask
        )


def fusion_loss(
    logits: jnp.ndarray,  # [b, 2]
    labels: jnp.ndarray,  # [b] int
    mask: jnp.ndarray,  # [b] bool — real example AND graph found
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(mean CE loss over real examples, softmax probs). The reference's
    ``CrossEntropyLoss`` + softmax (``model.py:82-88``); masking replaces its
    drop-missing-rows dynamic batching."""
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    w = mask.astype(jnp.float32)
    loss = jnp.sum(ce * w) / jnp.maximum(jnp.sum(w), 1.0)
    return loss, nn.softmax(logits, axis=-1)
