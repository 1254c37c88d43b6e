"""Hierarchical two-level GGNN: whole-program scoring that never falls
off the fused kernels.

A merged file/repo CPG blows past the largest VMEM-admittable serving
bucket (4094 nodes), so whole-unit scoring cannot ride the per-function
ladder — and routing a merged graph to the megabatch segment twin would
abandon the fused-kernel MFU story the packer bought. The standard answer
(the "GNN Acceleration" survey's hierarchical composition + subgraph
reuse) maps cleanly onto DeepDFA's per-function embedding:

- **Level 1** — the existing fused/megabatch per-function GGNN, stopped
  at the pooled embedding: :func:`~deepdfa_tpu.ops.megabatch.
  fused_ggnn_encoder` is the SAME whole-model kernel (same param tree,
  same prologue/rounds/pooling epilogue) with the head matmuls elided,
  fed by this module's own first-fit-decreasing megabatch packer. Per-
  function embeddings are bit-identical to the standalone fused path —
  the packer and cache plumbing never perturb a bit (pinned in
  ``tests/test_hier.py``). Shapes the VMEM plan refuses route to
  :func:`~deepdfa_tpu.ops.megabatch.megabatch_encoder_reference` and are
  counted in ``n_fallback_dispatches`` — the bench gate holds that count
  at zero on every fixture unit.
- **Embedding cache** — a content-addressed
  :class:`~deepdfa_tpu.serve.embcache.FunctionEmbeddingCache` in front of
  level 1 (key = normalized function source × model_rev × vocab hash ×
  feature config), so a repo re-scan re-embeds only cache-missed
  functions and a warm rescan does ZERO level-1 dispatches.
- **Level 2** — :class:`CallGraphGGNN`, a small GGNN over the call graph:
  one node per function (its level-1 embedding concatenated with
  ``_DFA_ireach``/``_DFA_itaint`` interprocedural summaries), edges from
  :mod:`deepdfa_tpu.cpg.callgraph` (made bidirectional: taint travels
  caller→callee through params and callee→caller through returns),
  producing the unit-level score plus the per-function attribution
  readout that lands in ``scan.json``.

Level-2 parameters are derived deterministically from the level-1
``model_rev`` (the parameter content hash) — same checkpoint, same unit
scores, across processes and sessions.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import numpy as np

from deepdfa_tpu.config import ALL_SUBKEYS, GGNNConfig
from deepdfa_tpu.data.graphs import Graph, batch_np

__all__ = [
    "UnitFunction",
    "CallGraphGGNN",
    "HierScorer",
    "megabatch_compatible",
    "unit_call_edges",
    "unit_summaries",
    "N_SUMMARY_FEATURES",
]

# per-function interprocedural summary width fed to level 2 alongside the
# level-1 embedding: [log1p(n_nodes), log1p(Σ ireach), clip(max ireach)/8,
# max itaint / 3, any cross-boundary-only taint, log1p(callers),
# log1p(callees)]
N_SUMMARY_FEATURES = 7


def megabatch_compatible(cfg: GGNNConfig) -> bool:
    """Whether ``cfg`` is servable by the whole-model fused kernel — the
    same constraints :class:`~deepdfa_tpu.models.ggnn_megabatch.
    GGNNMegabatch` enforces at setup. Engines outside this envelope have
    no hierarchical path (``score_unit`` raises)."""
    return (cfg.concat_all_absdf
            and not cfg.dataflow_families
            and not cfg.interproc_families
            and cfg.label_style == "graph"
            and not cfg.encoder_mode
            and cfg.aggregation == "sum")


@dataclasses.dataclass(frozen=True)
class UnitFunction:
    """One function of a scoring unit: the name the call graph resolves,
    the source text the embedding cache keys on, and the encoded graph
    level 1 embeds on a miss."""

    name: str
    code: str
    graph: Graph


# ---------------------------------------------------------------------------
# level 2: the call-graph GGNN


def _build_level2(hidden: int, n_steps: int):
    import flax.linen as nn
    import jax.numpy as jnp

    from deepdfa_tpu.models.ggnn import GRUCell

    class CallGraphGGNN(nn.Module):
        """Small GGNN over the call graph (one node per function).

        in_proj compresses ``concat([level-1 embedding, summaries])`` to
        the hidden width, ``n_steps`` message rounds run over the
        bidirectional call edges (Dense message + segment-sum + GRU — the
        level-1 update rule at call-graph scale), and the readout mirrors
        ``GlobalAttentionPooling``: a masked softmax gate pools the unit
        embedding for the unit head, while a per-node head emits the
        per-function attribution logits. Units are a handful of nodes, so
        this runs as plain XLA — no bucket ladder, no VMEM plan.
        """

        hidden: int
        n_steps: int

        @nn.compact
        def __call__(self, emb, senders, receivers, mask):
            import jax

            n = emb.shape[0]
            h = jnp.tanh(nn.Dense(self.hidden, name="in_proj")(emb))
            h0 = h
            edge = nn.Dense(self.hidden, name="edge_linear")
            gru = GRUCell(self.hidden, name="gru")
            for _ in range(self.n_steps):
                msg = edge(h)
                agg = jax.ops.segment_sum(
                    msg[senders], receivers, num_segments=n)
                h = gru(agg, h)
            hcat = jnp.concatenate([h, h0], axis=-1)
            gate_logit = nn.Dense(1, name="gate")(hcat)[:, 0]
            gate_logit = jnp.where(mask, gate_logit, -jnp.inf)
            gate = jax.nn.softmax(gate_logit)
            pooled = jnp.sum(gate[:, None] * hcat, axis=0)
            unit_logit = nn.Dense(1, name="out")(pooled)[0]
            fn_logit = nn.Dense(1, name="attr")(hcat)[:, 0]
            return unit_logit, fn_logit, gate

    return CallGraphGGNN(hidden=hidden, n_steps=n_steps)


def CallGraphGGNN(hidden: int = 32, n_steps: int = 2):
    """Construct the level-2 flax module (factory so flax stays a deferred
    import — see :func:`_build_level2` for the architecture)."""
    return _build_level2(hidden, n_steps)


# ---------------------------------------------------------------------------
# supergraph → level-2 inputs


def unit_call_edges(sg, names: Sequence[str]) -> tuple[np.ndarray, np.ndarray]:
    """Call-graph edges of ``sg`` mapped onto unit-function indices,
    bidirectional (taint flows both ways across a call boundary) with one
    self-loop per function so isolated functions still see their own
    state. Edges touching a method outside ``names`` are dropped."""
    index = {name: i for i, name in enumerate(names)}
    pairs: set[tuple[int, int]] = {(i, i) for i in range(len(names))}
    for caller_mid, callee_mid in sg.callgraph.edges:
        a = index.get(sg.method_names.get(caller_mid, ""))
        b = index.get(sg.method_names.get(callee_mid, ""))
        if a is None or b is None:
            continue
        pairs.add((a, b))
        pairs.add((b, a))
    ordered = sorted(pairs)
    senders = np.asarray([a for a, _ in ordered], np.int32)
    receivers = np.asarray([b for _, b in ordered], np.int32)
    return senders, receivers


def unit_summaries(sg, names: Sequence[str]) -> np.ndarray:
    """``[len(names), N_SUMMARY_FEATURES]`` per-function interprocedural
    summaries — the ``_DFA_ireach``/``_DFA_itaint`` node features of
    :func:`~deepdfa_tpu.cpg.interproc.interproc_node_features` folded to
    one row per function, computed on the supergraph the caller already
    built (no re-parse, no re-supergraph)."""
    from deepdfa_tpu.cpg.interproc import interproc_node_features

    feats = interproc_node_features(sg.base, sg=sg)
    mid_of = {name: mid for mid, name in sg.method_names.items()}
    by_owner: dict[int, list[int]] = {}
    for nid in sg.base.nodes:
        mid = sg.owner.get(nid)
        if mid is not None:
            by_owner.setdefault(mid, []).append(nid)
    callers: dict[int, int] = {}
    callees: dict[int, int] = {}
    for a, b in sg.callgraph.edges:
        callees[a] = callees.get(a, 0) + 1
        callers[b] = callers.get(b, 0) + 1
    out = np.zeros((len(names), N_SUMMARY_FEATURES), np.float32)
    for i, name in enumerate(names):
        mid = mid_of.get(name)
        if mid is None:
            continue
        nodes = by_owner.get(mid, [])
        ireach = [feats["ireach"].get(n, 0) for n in nodes]
        itaint = [feats["itaint"].get(n, 0) for n in nodes]
        out[i] = [
            math.log1p(len(nodes)),
            math.log1p(float(sum(ireach))),
            min(max(ireach, default=0), 8) / 8.0,
            max(itaint, default=0) / 3.0,
            1.0 if any(c >= 3 for c in itaint) else 0.0,
            math.log1p(float(callers.get(mid, 0))),
            math.log1p(float(callees.get(mid, 0))),
        ]
    return out


# ---------------------------------------------------------------------------
# the scorer


class HierScorer:
    """Two-level whole-unit scorer over a level-1 GGNN parameter tree.

    ``params`` is the (f32) parameter tree every layout shares
    (``embed_{sk}``/``ggnn``/``pooling`` — the head is never read);
    ``cfg``/``input_dim`` must be megabatch-compatible. ``cache`` (a
    :class:`~deepdfa_tpu.serve.embcache.FunctionEmbeddingCache`) is
    consulted before any level-1 work and written after; attach or swap
    it freely — it only ever stores finished embeddings.

    Counters (the bench gates read them): ``n_level1_dispatches`` fused-
    kernel launches, ``n_fallback_dispatches`` segment-twin launches
    (plan-refused shapes — held at zero on fixture units),
    ``level1_recompute`` functions embedded rather than served from
    cache.
    """

    #: level-1 megabatch admission budget per packed bin (graphs, nodes,
    #: edges) — far under the VMEM plan for the flagship config; the plan
    #: itself is still checked per bin and is what routing obeys
    MAX_BIN_GRAPHS = 64
    MAX_BIN_NODES = 4094

    def __init__(self, cfg: GGNNConfig, input_dim: int, params, *,
                 cache=None, model_rev: str | None = None,
                 level2_hidden: int = 32, level2_steps: int = 2):
        if not megabatch_compatible(cfg):
            raise ValueError(
                "HierScorer needs a megabatch-compatible level-1 config "
                "(concat_all_absdf=True, graph labels, sum aggregation, no "
                "dataflow/interproc families, no encoder_mode) — the whole "
                "point is that level 1 never leaves the fused kernels")
        import jax.numpy as jnp

        self.cfg = cfg
        self.input_dim = int(input_dim)
        self.cache = cache
        self.n_level1_dispatches = 0
        self.n_fallback_dispatches = 0
        self.level1_recompute = 0
        self.out_dim = 2 * cfg.hidden_dim * len(ALL_SUBKEYS)
        self._width = cfg.hidden_dim * len(ALL_SUBKEYS)

        p = params
        f32 = lambda a: jnp.asarray(a, jnp.float32)
        self._table = jnp.concatenate(
            [f32(p[f"embed_{sk}"]["embedding"]) for sk in ALL_SUBKEYS], axis=0)
        conv = p["ggnn"]
        self._ew, self._eb = (f32(conv["edge_linear"]["kernel"]),
                              f32(conv["edge_linear"]["bias"]))
        self._xw, self._xb = (f32(conv["gru"]["x_proj"]["kernel"]),
                              f32(conv["gru"]["x_proj"]["bias"]))
        self._hw, self._hb = (f32(conv["gru"]["h_proj"]["kernel"]),
                              f32(conv["gru"]["h_proj"]["bias"]))
        self._gw, self._gb = (f32(p["pooling"]["gate"]["kernel"]),
                              f32(p["pooling"]["gate"]["bias"]))
        if model_rev is None:
            from deepdfa_tpu.serve.engine import params_content_hash

            model_rev = params_content_hash(params)
        self.model_rev = model_rev
        self._level2 = _build_level2(level2_hidden, level2_steps)
        self._l2_params = self._init_level2()

    # -- level 2 init --------------------------------------------------------

    def _init_level2(self):
        """Level-2 params seeded from the level-1 model_rev: the derived
        head is a deterministic function of the checkpoint it extends.
        Hashing (rather than parsing) the revision keeps any string —
        content hash, artifact tag, test stub — a valid seed source."""
        import hashlib

        import jax
        import jax.numpy as jnp

        seed = int.from_bytes(
            hashlib.sha256(self.model_rev.encode()).digest()[:4], "big")
        emb = jnp.zeros((2, self.out_dim + N_SUMMARY_FEATURES), jnp.float32)
        snd = jnp.asarray([0, 1], jnp.int32)
        rcv = jnp.asarray([0, 1], jnp.int32)
        mask = jnp.ones(2, bool)
        return self._level2.init(
            jax.random.key(seed), emb, snd, rcv, mask)["params"]

    # -- level 1: pack + embed ----------------------------------------------

    def _plan(self, n_graphs: int, n_nodes: int, n_edges: int):
        from deepdfa_tpu.ops.megabatch import MegabatchPlan, _round_up

        return MegabatchPlan(
            max_graphs=n_graphs + 1,
            max_nodes=_round_up(max(n_nodes + 1, 8), 8),
            max_edges=_round_up(max(n_edges, 1), 128),
            width=self._width,
            n_steps=self.cfg.n_steps,
            table_rows=self.input_dim * len(ALL_SUBKEYS),
            embed_width=self.cfg.hidden_dim,
            n_head_layers=0,
        )

    def _pack(self, graphs: Sequence[Graph]) -> list[tuple[list[int], object]]:
        """First-fit-decreasing pack ``graphs`` into megabatch bins, each
        admitted by the padded VMEM plan; returns ``(indices, plan)`` per
        bin. Unlike :func:`~deepdfa_tpu.ops.megabatch.pack_megabatches`
        (which drops graph identity) every bin remembers which input
        graphs it carries — the embeddings must land back in order."""
        order = sorted(range(len(graphs)),
                       key=lambda i: (-graphs[i].n_nodes,
                                      -graphs[i].n_edges, i))
        bins: list[list[int]] = []
        loads: list[list[int]] = []  # [node-sum, edge-sum]
        for i in order:
            g = graphs[i]
            for b, load in zip(bins, loads):
                if len(b) >= self.MAX_BIN_GRAPHS:
                    continue
                nn_, ne_ = load[0] + g.n_nodes, load[1] + g.n_edges
                if nn_ > self.MAX_BIN_NODES:
                    continue
                if self._plan(len(b) + 1, nn_, ne_).fits:
                    b.append(i)
                    load[0], load[1] = nn_, ne_
                    break
            else:
                bins.append([i])
                loads.append([g.n_nodes, g.n_edges])
        return [(b, self._plan(len(b), load[0], load[1]))
                for b, load in zip(bins, loads)]

    def _embed_batch(self, batch) -> np.ndarray:
        """One packed batch → pooled embeddings ``[max_graphs, out_dim]``
        through the fused encoder, or the bit-identical segment twin when
        the plan refuses the realized shape."""
        import jax
        import jax.numpy as jnp

        from deepdfa_tpu.ops.megabatch import (
            fused_ggnn_encoder,
            megabatch_encoder_reference,
        )

        ids = jnp.stack(
            [jnp.asarray(batch.node_feats[f"_ABS_DATAFLOW_{sk}"])
             + i * self.input_dim
             for i, sk in enumerate(ALL_SUBKEYS)], axis=-1)
        plan = self._plan(batch.max_graphs - 1, batch.max_nodes - 1,
                          batch.senders.shape[0])
        args = (self._table, ids, jnp.asarray(batch.senders),
                jnp.asarray(batch.receivers), jnp.asarray(batch.node_gidx),
                jnp.asarray(batch.node_mask), self._ew, self._eb,
                self._xw, self._xb, self._hw, self._hb, self._gw, self._gb)
        if plan.fits:
            self.n_level1_dispatches += 1
            out = fused_ggnn_encoder(
                *args, n_steps=self.cfg.n_steps, n_graphs=batch.max_graphs,
                interpret=jax.default_backend() != "tpu", edges_sorted=True)
        else:
            self.n_fallback_dispatches += 1
            out = megabatch_encoder_reference(
                *args, n_steps=self.cfg.n_steps, n_graphs=batch.max_graphs,
                edges_sorted=True)
        return np.asarray(out, np.float32)

    def embed_graphs(self, graphs: Sequence[Graph]) -> np.ndarray:
        """Embed ``graphs`` through the megabatch packer + fused encoder —
        the standalone level-1 path (no cache): ``[len(graphs), out_dim]``
        in input order. This is the bit-identity baseline the hier tests
        pin :meth:`embed_functions` against."""
        out = np.zeros((len(graphs), self.out_dim), np.float32)
        for indices, plan in self._pack(graphs):
            batch = batch_np([graphs[i] for i in indices], plan.max_graphs,
                             plan.max_nodes, plan.max_edges)
            embs = self._embed_batch(batch)
            for slot, i in enumerate(indices):
                out[i] = embs[slot]
        return out

    def embed_functions(self, fns: Sequence[UnitFunction]) -> np.ndarray:
        """Cache-fronted level 1: consult the embedding cache per function,
        pack + embed only the misses, commit them back. A warm cache makes
        this ZERO dispatches (the bench's warm-rescan gate)."""
        out = np.zeros((len(fns), self.out_dim), np.float32)
        misses: list[tuple[int, str | None]] = []
        for i, fn in enumerate(fns):
            if self.cache is not None:
                key = self.cache.key(fn.code)
                hit = self.cache.get(key)
                if hit is not None and hit.size == self.out_dim:
                    out[i] = hit
                    continue
                misses.append((i, key))
            else:
                misses.append((i, None))
        if misses:
            embs = self.embed_graphs([fns[i].graph for i, _ in misses])
            self.level1_recompute += len(misses)
            for (i, key), e in zip(misses, embs):
                out[i] = e
                if self.cache is not None and key is not None:
                    self.cache.put(key, e)
        return out

    # -- level 2: the unit score ---------------------------------------------

    def score_unit(self, fns: Sequence[UnitFunction], sg) -> dict:
        """Score one merged unit as ONE request: level-1 embeddings (cache-
        fronted, fused-kernel) composed by the call-graph GGNN into a unit
        score plus per-function attribution. ``sg`` is the unit's
        :class:`~deepdfa_tpu.cpg.interproc.Supergraph` (the scan already
        built it for the taint differential)."""
        import jax
        import jax.numpy as jnp

        if not fns:
            raise ValueError("score_unit needs at least one function")
        names = [fn.name for fn in fns]
        embs = self.embed_functions(fns)
        summaries = unit_summaries(sg, names)
        senders, receivers = unit_call_edges(sg, names)
        x = jnp.concatenate(
            [jnp.asarray(embs), jnp.asarray(summaries)], axis=-1)
        mask = jnp.ones(len(fns), bool)
        unit_logit, fn_logit, gate = self._level2.apply(
            {"params": self._l2_params}, x, jnp.asarray(senders),
            jnp.asarray(receivers), mask)
        unit_p = float(jax.nn.sigmoid(unit_logit))
        fn_p = np.asarray(jax.nn.sigmoid(fn_logit), np.float32)
        gate = np.asarray(gate, np.float32)
        attribution = sorted(
            ({"function": name, "weight": round(float(w), 6),
              "score": round(float(p), 6)}
             for name, w, p in zip(names, gate, fn_p)),
            key=lambda row: -row["weight"])
        return {
            "unit_score": round(unit_p, 6),
            "attribution": attribution,
            "n_functions": len(fns),
            "call_edges": int(sg.n_call_edges),
            "level1": self.stats(),
        }

    # -- accounting -----------------------------------------------------------

    def stats(self) -> dict:
        return {
            "dispatches": self.n_level1_dispatches,
            "fallback_dispatches": self.n_fallback_dispatches,
            "recompute": self.level1_recompute,
            "cache": self.cache.stats() if self.cache is not None else None,
        }

    def reset_counters(self) -> None:
        self.n_level1_dispatches = 0
        self.n_fallback_dispatches = 0
        self.level1_recompute = 0
