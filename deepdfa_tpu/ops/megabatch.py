"""Cross-bucket megabatch packing + whole-model fusion for the GGNN.

Two pieces, both aimed at the same r05 number — 3.6% chained MFU with the
hidden-32 matmuls memory-bound and the TPU idle between small dispatches
(ROADMAP direction 4; the cure is the one Morphling and arXiv:1906.11786
prescribe: pack sparse graphs into dense hardware-shaped blocks and
dispatch less):

- **Megabatch packing** — :func:`pack_megabatches` greedily first-fits many
  small graphs from *different* size buckets into one block-diagonal
  segment layout (a plain :class:`~deepdfa_tpu.data.graphs.BatchedGraphs`:
  node rows are contiguous per graph, edges stay receiver-sorted, so the
  packed batch is bit-compatible with every existing layout). Admission is
  byte-exact: a candidate bin is grown only while
  :func:`megabatch_working_set_bytes` — the padded-shape VMEM plan of the
  whole-model kernel — stays under the cap. The 126-node bucket stops
  wasting lanes because its graphs ride in the same launch as everyone
  else's.

- **Whole-model fusion** — :func:`fused_ggnn_model` runs
  embed → messages → GRU → attention pool → label head in ONE Pallas
  launch. The grid is ``(n_steps + 1,)``: step 0 gathers the stacked
  embedding table into VMEM-resident node states, steps ``0..n_steps-1``
  are the fused message rounds (identical math to
  :mod:`deepdfa_tpu.ops.fused_ggnn`), and the extra final step runs the
  pooling softmax and the classifier head off the still-resident states —
  the pooling/head XLA dispatches of the per-op path disappear. The
  per-graph softmax and readout are driven by a node→graph one-hot matrix
  built in-kernel from ``node_gidx``, so the reductions are MXU matmuls
  instead of scatters.

Differentiable via the existing ``custom_vjp`` recompute pattern extended
to the new epilogue: the backward banks the (tiny) inputs and reverse-
differentiates :func:`megabatch_reference` — the same math in plain XLA
segment ops, which doubles as the bit-identical segment-twin path that
over-plan megabatches route to (:class:`~deepdfa_tpu.models.ggnn_megabatch.
GGNNMegabatch` checks the plan statically per bucket shape).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepdfa_tpu.data.graphs import BatchedGraphs, Graph, batch_np, padding_efficiency
from deepdfa_tpu.ops.fused_ggnn import (
    _COMPILER_PARAMS,
    SMEM_CAP_BYTES,
    VMEM_CAP_BYTES,
    _pad_conv_weights,
    _resident,
    _round_up,
    _unrolled_reference,
    edge_smem_bytes,
    working_set_bytes,
)
from deepdfa_tpu.ops.segment import segment_softmax, segment_sum

__all__ = [
    "MegabatchPlan",
    "PackResult",
    "megabatch_working_set_bytes",
    "megabatch_smem_bytes",
    "fits_vmem_megabatch",
    "pack_megabatches",
    "fused_ggnn_model",
    "fused_ggnn_encoder",
    "megabatch_reference",
    "megabatch_encoder_reference",
]


def megabatch_working_set_bytes(
    n_nodes: int,
    n_edges: int,
    width: int,
    n_graphs: int,
    *,
    table_rows: int,
    embed_width: int,
    n_head_layers: int,
) -> int:
    """Conservative VMEM working set of the WHOLE-MODEL fused kernel for one
    megabatch shape, counted over exactly the padded blocks the wrapper
    builds (the ``working_set_bytes`` discipline: plan what you allocate).

    On top of the message-passing forward's blocks (node states, GRU temps,
    conv weights, edge vectors) the single launch must also hold: the
    lane-placed embedding table (full conv width per row — ``embed_width``
    only fixes which lanes a sub-table owns), the node→graph one-hot
    matrix and its masked-max temp (the pooling softmax runs as MXU
    matmuls against it), the ``concat([h, h0])`` block, the gate/head
    weights, and the per-graph activations of the classifier head. The id
    rows are scalar-prefetched (:func:`megabatch_smem_bytes`); their
    sublane-padded size stays in this count as headroom, like the edges'.
    """
    np_ = _round_up(max(n_nodes, 8), 8)
    dp = _round_up(max(width, 1), 128)
    gp = _round_up(max(n_graphs, 1), 128)
    tp = _round_up(max(table_rows, 8), 8)
    npl = _round_up(np_, 128)
    base = working_set_bytes(n_nodes, n_edges, width)
    table = tp * dp * 4
    ids = 8 * npl * 4
    gidx_mask = 2 * np_ * 128 * 4          # gidx + node-mask columns
    onehot = 2 * np_ * gp * 4              # M and the masked-max temp S
    hcat = np_ * 2 * dp * 4                # concat([h, h0])
    vec_temps = 6 * np_ * 128 * 4          # gate logits/exp/gather temps
    gate_w = (2 * dp * 128 + 128) * 4
    layers = max(n_head_layers, 1)
    head_w = ((layers - 1) * (2 * dp * 2 * dp + 2 * dp)
              + (2 * dp * 128 + 128)) * 4
    head_act = 3 * gp * 2 * dp * 4         # pooled + ping-pong activations
    out = gp * 128 * 4
    small = 4 * gp * 128 * 4               # per-graph max/denominator rows
    return (base + table + ids + gidx_mask + onehot + hcat + vec_temps
            + gate_w + head_w + head_act + out + small)


def megabatch_smem_bytes(n_nodes: int, n_edges: int, n_sub: int) -> int:
    """SMEM footprint of the whole-model kernel's scalar-prefetched
    operands: the edge vectors plus ``n_sub`` id rows of the padded node
    count (flattened 1-D int32, as the wrapper builds them)."""
    np_ = _round_up(max(n_nodes, 8), 8)
    return edge_smem_bytes(n_edges) + n_sub * np_ * 4


def fits_vmem_megabatch(
    n_nodes: int,
    n_edges: int,
    width: int,
    n_graphs: int,
    *,
    table_rows: int,
    embed_width: int,
    n_head_layers: int,
) -> bool:
    """Whether a megabatch shape is safe for the whole-model kernel. Shapes
    over the plan route bit-identically to the segment twin
    (:func:`megabatch_reference`) — correctness is never gated on VMEM."""
    n_sub = max(width // max(embed_width, 1), 1)
    return (
        megabatch_working_set_bytes(
            n_nodes, n_edges, width, n_graphs, table_rows=table_rows,
            embed_width=embed_width, n_head_layers=n_head_layers,
        ) <= VMEM_CAP_BYTES
        and megabatch_smem_bytes(n_nodes, n_edges, n_sub) <= SMEM_CAP_BYTES
    )


@dataclasses.dataclass(frozen=True)
class MegabatchPlan:
    """Static shape + VMEM plan of one megabatch (the packer's admission
    record; also what the model/Trainer consult to route over-plan shapes
    to the segment twin)."""

    max_graphs: int
    max_nodes: int
    max_edges: int
    width: int
    n_steps: int
    table_rows: int
    embed_width: int
    n_head_layers: int

    @property
    def working_set(self) -> int:
        return megabatch_working_set_bytes(
            self.max_nodes, self.max_edges, self.width, self.max_graphs,
            table_rows=self.table_rows, embed_width=self.embed_width,
            n_head_layers=self.n_head_layers,
        )

    @property
    def fits(self) -> bool:
        return fits_vmem_megabatch(
            self.max_nodes, self.max_edges, self.width, self.max_graphs,
            table_rows=self.table_rows, embed_width=self.embed_width,
            n_head_layers=self.n_head_layers,
        )


@dataclasses.dataclass
class PackResult:
    """Output of :func:`pack_megabatches`: the packed batches, one
    :class:`MegabatchPlan` per batch (same order), graphs too large for
    even a single-graph plan (routed to the per-bucket ladder / segment
    twin by the caller), and the overall padding efficiency."""

    batches: list[BatchedGraphs]
    plans: list[MegabatchPlan]
    oversize: list[Graph]
    efficiency: dict[str, float]


def pack_megabatches(
    graphs: Sequence[Graph],
    *,
    width: int,
    n_steps: int,
    table_rows: int,
    embed_width: int,
    n_head_layers: int,
    max_batch_graphs: int = 256,
    node_round: int = 8,
    edge_round: int = 128,
    uniform: bool = False,
) -> PackResult:
    """Greedy first-fit-decreasing packer with byte-exact VMEM admission.

    Graphs are sorted by node count (decreasing — the classic FFD bound)
    and each is placed into the first open bin whose grown padded shape
    still passes :func:`fits_vmem_megabatch`; otherwise a new bin opens.
    Graph slots are NOT quantized (``max_graphs = n_real + 1``: exactly one
    padding-sink slot per megabatch), so the graphs-axis padding
    efficiency of a bin holding n graphs is n/(n+1) — the ≥0.95 target is
    met by any bin of ≥19 graphs, which VMEM admits by orders of magnitude
    for corpus-scale graphs. Node/edge budgets quantize to ``node_round``/
    ``edge_round`` only, to bound compile count without burning lanes.

    ``uniform=True`` re-packs for ONE compiled shape (what a scanned bench
    chain or a warm serving shape needs): graphs are snake-dealt in
    decreasing size order across the smallest bin count whose elementwise-
    max union plan passes VMEM, so bins differ by at most one graph and
    the shared shape is tight — greedy FFD followed by a union re-pad
    would bloat the union to the fullest bin and leave the last partial
    bin mostly padding (a 127+127+2 split of 256 graphs prices every bin
    at 128 slots: graphs efficiency 0.67 where balanced dealing gives
    0.98). ``plans`` repeats the union plan; its ``fits`` still must be
    consulted — when even balanced dealing finds no admitted union (a
    node-heavy plus an edge-heavy extreme), the FFD bins are kept and the
    caller routes over-plan shapes to the segment twin.
    """
    order = sorted(graphs, key=lambda g: (-g.n_nodes, -g.n_edges, g.gid))
    bins: list[dict] = []
    oversize: list[Graph] = []

    def _plan(n_real_graphs: int, nodes: int, edges: int) -> MegabatchPlan:
        return MegabatchPlan(
            max_graphs=n_real_graphs + 1,
            max_nodes=_round_up(nodes + 1, node_round),
            max_edges=_round_up(max(edges, 1), edge_round),
            width=width,
            n_steps=n_steps,
            table_rows=table_rows,
            embed_width=embed_width,
            n_head_layers=n_head_layers,
        )

    for g in order:
        if not _plan(1, g.n_nodes, g.n_edges).fits:
            oversize.append(g)
            continue
        placed = False
        for b in bins:
            if len(b["graphs"]) + 1 > max_batch_graphs:
                continue
            if _plan(len(b["graphs"]) + 1, b["nodes"] + g.n_nodes,
                     b["edges"] + g.n_edges).fits:
                b["graphs"].append(g)
                b["nodes"] += g.n_nodes
                b["edges"] += g.n_edges
                placed = True
                break
        if not placed:
            bins.append({"graphs": [g], "nodes": g.n_nodes, "edges": g.n_edges})

    batches: list[BatchedGraphs] = []
    plans: list[MegabatchPlan] = []
    if uniform and bins:
        placed = [g for b in bins for g in b["graphs"]]
        placed.sort(key=lambda g: (-g.n_nodes, -g.n_edges, g.gid))
        ffd_union = _plan(max(len(b["graphs"]) for b in bins),
                          max(b["nodes"] for b in bins),
                          max(b["edges"] for b in bins))

        def _deal(n_bins: int) -> list[list[Graph]]:
            dealt: list[list[Graph]] = [[] for _ in range(n_bins)]
            for i, g in enumerate(placed):
                row, col = divmod(i, n_bins)
                dealt[col if row % 2 == 0 else n_bins - 1 - col].append(g)
            return dealt

        n_min = max(1, -(-len(placed) // max_batch_graphs))
        chosen = union = None
        for nb in range(n_min, len(placed) + 1):
            if nb > len(bins) and ffd_union.fits:
                break  # FFD already admits with fewer bins — no regression
            cand = _deal(nb)
            u = _plan(max(len(d) for d in cand),
                      max(sum(g.n_nodes for g in d) for d in cand),
                      max(sum(g.n_edges for g in d) for d in cand))
            if u.fits:
                chosen, union = cand, u
                break
        if chosen is None:
            chosen = [b["graphs"] for b in bins]
            union = ffd_union
        for d in chosen:
            batches.append(
                batch_np(d, union.max_graphs, union.max_nodes,
                         union.max_edges)
            )
            plans.append(union)
    else:
        for b in bins:
            plan = _plan(len(b["graphs"]), b["nodes"], b["edges"])
            assert plan.fits, "packer admitted a bin its own plan refuses"
            batches.append(
                batch_np(b["graphs"], plan.max_graphs, plan.max_nodes,
                         plan.max_edges)
            )
            plans.append(plan)
    eff = padding_efficiency(batches) if batches else {
        "nodes": 0.0, "edges": 0.0, "graphs": 0.0}
    return PackResult(batches=batches, plans=plans, oversize=oversize,
                      efficiency=eff)


# --------------------------------------------------------------------------
# whole-model fused kernel
# --------------------------------------------------------------------------


def _model_kernel(ids_ref, snd_ref, rcv_ref, table_ref, gidx_ref, mask_ref,
                  ew_ref, eb_ref, xw_ref, xb_ref, hw_ref, hb_ref,
                  gw_ref, gb_ref, *rest, n_nodes: int, n_edges: int,
                  n_sub: int, ids_stride: int, width: int, n_steps: int,
                  gp: int, n_layers: int, encoder: bool = False):
    """One grid step of the whole-model forward. Grid ``(n_steps + 1,)``,
    executed sequentially on TPU, so the node-state scratch persists across
    the prologue, every message round, and the epilogue:

    - step 0 prologue: gather the stacked embedding table rows into the
      node states. The wrapper lays sub-table ``k``'s rows out in lanes
      ``[k·embed, (k+1)·embed)`` of a full-width row (zeros elsewhere), so
      a node's embedding is the SUM of its ``n_sub`` gathered rows — exact
      in f32 (``x + 0``), and every load/store is a whole 128-lane row
      instead of a 32-lane write at a lane offset. The ids are scalar-
      prefetched (SMEM, ``ids_ref[k·ids_stride + i]``): they address VMEM
      rows. A copy is banked for the classifier concat;
    - steps ``0..n_steps-1``: the fused message round (identical math to
      ``ops.fused_ggnn._kernel``);
    - step ``n_steps`` epilogue: attention pooling as matmuls against the
      in-kernel node→graph one-hot ``M`` (masked per-graph max, shifted
      exp, denominator, weighted readout — ``segment_softmax`` semantics
      exactly, including zeroing the max and unit denominator of empty
      padding graphs) followed by the head matmuls, with relu between.
    """
    head = rest[: 2 * n_layers]
    out_ref = rest[2 * n_layers]
    hcur_ref, h0s_ref, msg_ref, agg_ref, hcat_ref = rest[2 * n_layers + 1:]
    step = pl.program_id(0)
    d = width
    f32 = jnp.float32

    @pl.when(step == 0)
    def _embed():
        hcur_ref[:] = jnp.zeros_like(hcur_ref)

        def node_body(i, carry):
            row = table_ref[pl.ds(ids_ref[i], 1), :]
            for k in range(1, n_sub):
                row = row + table_ref[pl.ds(ids_ref[k * ids_stride + i], 1), :]
            hcur_ref[pl.ds(i, 1), :] = row
            return carry

        jax.lax.fori_loop(0, n_nodes, node_body, 0)
        h0s_ref[:] = hcur_ref[:]

    @pl.when(step < n_steps)
    def _round():
        h = hcur_ref[:]
        msg_ref[:] = (
            jnp.dot(h, ew_ref[:], preferred_element_type=f32) + eb_ref[:]
        )
        agg_ref[:] = jnp.zeros_like(agg_ref)

        def edge_body(e, carry):
            s = snd_ref[e]
            r = rcv_ref[e]
            agg_ref[pl.ds(r, 1), :] += msg_ref[pl.ds(s, 1), :]
            return carry

        jax.lax.fori_loop(0, n_edges, edge_body, 0)
        xp = jnp.dot(agg_ref[:], xw_ref[:], preferred_element_type=f32) + xb_ref[:]
        hp = jnp.dot(h, hw_ref[:], preferred_element_type=f32) + hb_ref[:]
        r = jax.nn.sigmoid(xp[:, :d] + hp[:, :d])
        z = jax.nn.sigmoid(xp[:, d:2 * d] + hp[:, d:2 * d])
        n = jnp.tanh(xp[:, 2 * d:] + r * hp[:, 2 * d:])
        hcur_ref[:] = (1.0 - z) * n + z * h

    @pl.when(step == n_steps)
    def _epilogue():
        hcat_ref[:, :d] = hcur_ref[:]
        hcat_ref[:, d:] = h0s_ref[:]
        hcat = hcat_ref[:]
        s = jnp.dot(hcat, gw_ref[:], preferred_element_type=f32) + gb_ref[:]
        s0 = s[:, :1]                                       # (np_, 1)
        gcol = gidx_ref[:, :1]                              # (np_, 1) int32
        mcol = mask_ref[:, :1]                              # (np_, 1) f32
        iota = jax.lax.broadcasted_iota(jnp.int32, (s0.shape[0], gp), 1)
        m_onehot = jnp.where(gcol == iota, 1.0, 0.0) * mcol  # (np_, gp)
        big = jnp.float32(1e30)
        masked = m_onehot * s0 + (m_onehot - 1.0) * big
        smax = jnp.max(masked, axis=0, keepdims=True)       # (1, gp)
        # padding-only graph columns max to -big; zero them so the shifted
        # exp stays finite (segment_softmax's isfinite guard)
        smax = jnp.where(smax > -0.5 * big, smax, 0.0)
        contract_cols = (((1,), (1,)), ((), ()))
        contract_rows = (((0,), (0,)), ((), ()))
        m_node = jax.lax.dot_general(
            m_onehot, smax, contract_cols, preferred_element_type=f32)
        e = mcol * jnp.exp(s0 - m_node)                     # (np_, 1)
        denom = jax.lax.dot_general(
            m_onehot, e, contract_rows, preferred_element_type=f32)  # (gp, 1)
        denom = jnp.where(denom == 0.0, 1.0, denom)
        dnode = jax.lax.dot_general(
            m_onehot, denom, (((1,), (0,)), ((), ())),
            preferred_element_type=f32)                     # (np_, 1)
        dnode = jnp.where(dnode == 0.0, 1.0, dnode)
        gate = e / dnode
        pooled = jax.lax.dot_general(
            m_onehot, gate * hcat, contract_rows,
            preferred_element_type=f32)                     # (gp, 2·dp)
        if encoder:
            # the hierarchical level-1 readout: stop at the pooled
            # function embedding — same prologue, same message rounds,
            # same pooling softmax, no head (models/ggnn_hier.py)
            out_ref[:] = pooled
            return
        a = pooled
        for li in range(n_layers):
            a = jnp.dot(a, head[2 * li][:], preferred_element_type=f32) + head[2 * li + 1][:]
            if li != n_layers - 1:
                a = jnp.maximum(a, 0.0)
        out_ref[:] = a


def _pack_half_rows(w: jnp.ndarray, d: int, dp: int, out_cols: int) -> jnp.ndarray:
    """Pad a ``[2d, out]`` weight whose rows index ``concat([h, h0])`` to
    ``[2dp, out_cols]``: the h/h0 halves must stay aligned to the PADDED
    width or the kernel's concat at ``dp`` boundaries would mix them."""
    out = w.shape[1]
    w2 = w.reshape(2, d, out)
    w2 = jnp.pad(w2, ((0, 0), (0, dp - d), (0, out_cols - out)))
    return w2.reshape(2 * dp, out_cols)


def _pack_half_cols(w2: jnp.ndarray, d: int, dp: int) -> jnp.ndarray:
    """Pad the OUTPUT axis of an already row-packed ``[2dp, 2d]`` weight to
    the half-block layout ``[2dp, 2dp]`` (hidden head layers keep the
    packed activation layout end to end)."""
    rows = w2.shape[0]
    w3 = w2.reshape(rows, 2, d)
    w3 = jnp.pad(w3, ((0, 0), (0, 0), (0, dp - d)))
    return w3.reshape(rows, 2 * dp)


def _pack_half_bias(b: jnp.ndarray, d: int, dp: int) -> jnp.ndarray:
    b2 = jnp.pad(b.reshape(2, d), ((0, 0), (0, dp - d)))
    return b2.reshape(1, 2 * dp)


def _model_call(table, ids, senders, receivers, gidx, mask,
                ew, eb, xw, xb, hw, hb, gw, gb, head, *,
                n_steps: int, n_graphs: int, interpret: bool,
                encoder: bool) -> jnp.ndarray:
    """Pad every operand to its tile, lay the embedding table out for the
    whole-row prologue gather, and launch :func:`_model_kernel` once.
    Returns the PADDED output block: ``(gp, 2·dp)`` pooled embeddings in
    the packed-half layout when ``encoder``, else ``(gp, 128)`` logits in
    lane 0."""
    n, n_sub = ids.shape
    e = senders.shape[0]
    d = ew.shape[0]
    ed = table.shape[1]
    t_rows = table.shape[0]
    if n_sub * ed != d:
        raise ValueError(
            f"embed width {n_sub}·{ed} != conv width {d} — the whole-model "
            "kernel requires the concat-subkey config (embed == hidden)")
    if t_rows % n_sub:
        raise ValueError(
            f"stacked table has {t_rows} rows, not a multiple of the "
            f"{n_sub} sub-tables")
    np_ = _round_up(max(n, 8), 8)
    dp = _round_up(max(d, 1), 128)
    ep = _round_up(max(e, 1), 128)
    gp = _round_up(max(n_graphs, 1), 128)
    tp = _round_up(max(t_rows, 8), 8)
    f32 = jnp.float32

    # sub-table k (rows [k·input_dim, (k+1)·input_dim)) lands in lanes
    # [k·ed, (k+1)·ed): summing a node's n_sub gathered rows IS the concat
    sub = jnp.arange(t_rows, dtype=jnp.int32) // (t_rows // n_sub)
    lane_sub = jnp.arange(d, dtype=jnp.int32) // ed
    tablep = jnp.where(sub[:, None] == lane_sub[None, :],
                       jnp.tile(table.astype(f32), (1, n_sub)), 0.0)
    tablep = jnp.pad(tablep, ((0, tp - t_rows), (0, dp - d)))
    idsp = jnp.pad(ids.astype(jnp.int32).T, ((0, 0), (0, np_ - n))).reshape(-1)
    sndp = jnp.pad(senders.astype(jnp.int32), (0, ep - e))
    rcvp = jnp.pad(receivers.astype(jnp.int32), (0, ep - e))
    gidxp = jnp.pad(gidx.astype(jnp.int32)[:, None],
                    ((0, np_ - n), (0, 127)))
    maskp = jnp.pad(mask.astype(f32)[:, None], ((0, np_ - n), (0, 127)))
    ewp, ebp, xwp, xbp, hwp, hbp = _pad_conv_weights(
        ew, eb, xw, xb, hw, hb, d, dp)
    gwp = _pack_half_rows(gw.astype(f32), d, dp, 128)
    gbp = jnp.pad(gb.astype(f32), (0, 127)).reshape(1, 128)
    n_layers = len(head)
    head_p: list[jnp.ndarray] = []
    head_specs = []
    for li, (w, b) in enumerate(head):
        if li == n_layers - 1:
            head_p.append(_pack_half_rows(w.astype(f32), d, dp, 128))
            head_p.append(jnp.pad(b.astype(f32), (0, 127)).reshape(1, 128))
            head_specs += [_resident((2 * dp, 128)), _resident((1, 128))]
        else:
            wp = _pack_half_rows(w.astype(f32), d, dp, 2 * d)
            head_p.append(_pack_half_cols(wp, d, dp))
            head_p.append(_pack_half_bias(b.astype(f32), d, dp))
            head_specs += [_resident((2 * dp, 2 * dp)),
                           _resident((1, 2 * dp))]
    out_cols = 2 * dp if encoder else 128
    return pl.pallas_call(
        functools.partial(
            _model_kernel, n_nodes=n, n_edges=e, n_sub=n_sub, ids_stride=np_,
            width=dp, n_steps=n_steps, gp=gp, n_layers=n_layers,
            encoder=encoder),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,      # ids, senders, receivers → SMEM
            grid=(n_steps + 1,),
            in_specs=[
                _resident((tp, dp)),            # lane-placed embedding table
                _resident((np_, 128)),          # node_gidx column
                _resident((np_, 128)),          # node_mask column
                _resident((dp, dp)),            # edge_linear kernel
                _resident((1, dp)),             # edge_linear bias
                _resident((dp, 3 * dp)),        # gru x_proj kernel
                _resident((1, 3 * dp)),         # gru x_proj bias
                _resident((dp, 3 * dp)),        # gru h_proj kernel
                _resident((1, 3 * dp)),         # gru h_proj bias
                _resident((2 * dp, 128)),       # pooling gate kernel
                _resident((1, 128)),            # pooling gate bias
                *head_specs,
            ],
            out_specs=_resident((gp, out_cols)),
            scratch_shapes=[
                pltpu.VMEM((np_, dp), f32),       # hcur (node states)
                pltpu.VMEM((np_, dp), f32),       # h0 bank (classifier concat)
                pltpu.VMEM((np_, dp), f32),       # msg
                pltpu.VMEM((np_, dp), f32),       # agg
                pltpu.VMEM((np_, 2 * dp), f32),   # hcat
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((gp, out_cols), f32),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(idsp, sndp, rcvp, tablep, gidxp, maskp, ewp, ebp, xwp, xbp, hwp, hbp,
      gwp, gbp, *head_p)


def megabatch_reference(table, ids, senders, receivers, gidx, mask,
                        ew, eb, xw, xb, hw, hb, gw, gb, head, *,
                        n_steps: int, n_graphs: int,
                        edges_sorted: bool = True) -> jnp.ndarray:
    """The whole model in plain XLA segment ops — operation-for-operation
    the segment layout's math (``GGNN.__call__`` with ``GatedGraphConv`` /
    ``GlobalAttentionPooling``), so results are bit-identical to the
    segment twin on the same params. This is both the recompute the
    ``custom_vjp`` backward differentiates and the routing target for
    over-plan megabatches."""
    h0 = jnp.take(table, ids, axis=0).reshape(ids.shape[0], -1)
    h = _unrolled_reference(h0, senders, receivers, ew, eb, xw, xb, hw, hb,
                            n_steps, edges_sorted)
    hcat = jnp.concatenate([h, h0], axis=-1)
    gate_logit = (hcat @ gw + gb)[:, 0]
    gate = segment_softmax(gate_logit, gidx, n_graphs, mask=mask,
                           indices_are_sorted=True)
    pooled = segment_sum(gate[:, None] * hcat, gidx, n_graphs,
                         indices_are_sorted=True)
    a = pooled
    for i, (w, b) in enumerate(head):
        a = a @ w + b
        if i != len(head) - 1:
            a = jax.nn.relu(a)
    return a[..., 0].astype(jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("n_steps", "n_graphs", "edges_sorted"))
def megabatch_encoder_reference(table, ids, senders, receivers, gidx, mask,
                                ew, eb, xw, xb, hw, hb, gw, gb, *,
                                n_steps: int, n_graphs: int,
                                edges_sorted: bool = True) -> jnp.ndarray:
    """:func:`megabatch_reference` stopped at the pooled embedding — the
    segment-twin math of the hierarchical level-1 encoder (same ops, same
    order, no classifier head). Routing target for over-plan shapes in
    :class:`~deepdfa_tpu.models.ggnn_hier.HierScorer`."""
    h0 = jnp.take(table, ids, axis=0).reshape(ids.shape[0], -1)
    h = _unrolled_reference(h0, senders, receivers, ew, eb, xw, xb, hw, hb,
                            n_steps, edges_sorted)
    hcat = jnp.concatenate([h, h0], axis=-1)
    gate_logit = (hcat @ gw + gb)[:, 0]
    gate = segment_softmax(gate_logit, gidx, n_graphs, mask=mask,
                           indices_are_sorted=True)
    pooled = segment_sum(gate[:, None] * hcat, gidx, n_graphs,
                         indices_are_sorted=True)
    return pooled.astype(jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("n_steps", "n_graphs", "interpret",
                                    "edges_sorted"))
def fused_ggnn_encoder(
    table: jnp.ndarray,
    ids: jnp.ndarray,
    senders: jnp.ndarray,
    receivers: jnp.ndarray,
    gidx: jnp.ndarray,
    mask: jnp.ndarray,
    ew: jnp.ndarray,
    eb: jnp.ndarray,
    xw: jnp.ndarray,
    xb: jnp.ndarray,
    hw: jnp.ndarray,
    hb: jnp.ndarray,
    gw: jnp.ndarray,
    gb: jnp.ndarray,
    *,
    n_steps: int,
    n_graphs: int,
    interpret: bool = False,
    edges_sorted: bool = True,
) -> jnp.ndarray:
    """Whole-model fused forward WITHOUT the classifier head: embed →
    ``n_steps`` message rounds → GRU → attention pool, ONE Pallas launch,
    per-graph pooled embeddings ``[n_graphs, 2·width]`` out.

    The level-1 inner loop of the hierarchical scorer
    (:mod:`deepdfa_tpu.models.ggnn_hier`): identical prologue, rounds and
    pooling epilogue to :func:`fused_ggnn_model` — the SAME kernel with
    the head matmuls elided — so per-function embeddings come off the
    fused path the megabatch packer feeds, never a separate program.
    Inference-only (no custom_vjp: the hierarchical level 1 serves frozen
    params). Callers are expected to check :func:`fits_vmem_megabatch`
    and route over-plan shapes to :func:`megabatch_encoder_reference`.
    """
    d = ew.shape[0]
    dp = _round_up(max(d, 1), 128)
    out = _model_call(table, ids, senders, receivers, gidx, mask,
                      ew, eb, xw, xb, hw, hb, gw, gb, (),
                      n_steps=n_steps, n_graphs=n_graphs,
                      interpret=interpret, encoder=True)
    # unpad the packed-half layout [h (dp) | h0 (dp)] back to [2·d]
    return jnp.concatenate(
        [out[:n_graphs, :d], out[:n_graphs, dp:dp + d]], axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(15, 16, 17, 18))
def _megabatch_model(table, ids, senders, receivers, gidx, mask,
                     ew, eb, xw, xb, hw, hb, gw, gb, head,
                     n_steps: int, n_graphs: int, interpret: bool,
                     edges_sorted: bool):
    out = _model_call(table, ids, senders, receivers, gidx, mask,
                      ew, eb, xw, xb, hw, hb, gw, gb, head,
                      n_steps=n_steps, n_graphs=n_graphs,
                      interpret=interpret, encoder=False)
    return out[:n_graphs, 0]


def _megabatch_model_fwd(table, ids, senders, receivers, gidx, mask,
                         ew, eb, xw, xb, hw, hb, gw, gb, head,
                         n_steps, n_graphs, interpret, edges_sorted):
    out = _megabatch_model(table, ids, senders, receivers, gidx, mask,
                           ew, eb, xw, xb, hw, hb, gw, gb, head,
                           n_steps, n_graphs, interpret, edges_sorted)
    # recompute backward: bank the (tiny) inputs, not per-round states
    return out, (table, ids, senders, receivers, gidx, mask,
                 ew, eb, xw, xb, hw, hb, gw, gb, head)


def _megabatch_model_bwd(n_steps, n_graphs, interpret, edges_sorted, res, g):
    (table, ids, senders, receivers, gidx, mask,
     ew, eb, xw, xb, hw, hb, gw, gb, head) = res

    def ref(table_, ew_, eb_, xw_, xb_, hw_, hb_, gw_, gb_, head_):
        return megabatch_reference(
            table_.astype(jnp.float32), ids, senders, receivers, gidx, mask,
            ew_.astype(jnp.float32), eb_.astype(jnp.float32),
            xw_.astype(jnp.float32), xb_.astype(jnp.float32),
            hw_.astype(jnp.float32), hb_.astype(jnp.float32),
            gw_.astype(jnp.float32), gb_.astype(jnp.float32),
            jax.tree.map(lambda a: a.astype(jnp.float32), head_),
            n_steps=n_steps, n_graphs=n_graphs, edges_sorted=edges_sorted,
        )

    _, vjp = jax.vjp(ref, table, ew, eb, xw, xb, hw, hb, gw, gb, head)
    dtable, dew, deb, dxw, dxb, dhw, dhb, dgw, dgb, dhead = vjp(
        g.astype(jnp.float32))
    # integer/bool primals take float0 cotangents (JAX's tangent space)
    f0 = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return (dtable.astype(table.dtype), f0(ids), f0(senders), f0(receivers),
            f0(gidx), f0(mask), dew.astype(ew.dtype), deb.astype(eb.dtype),
            dxw.astype(xw.dtype), dxb.astype(xb.dtype), dhw.astype(hw.dtype),
            dhb.astype(hb.dtype), dgw.astype(gw.dtype), dgb.astype(gb.dtype),
            jax.tree.map(lambda t, x: t.astype(x.dtype), dhead, head))


_megabatch_model.defvjp(_megabatch_model_fwd, _megabatch_model_bwd)


@functools.partial(jax.jit,
                   static_argnames=("n_steps", "n_graphs", "interpret",
                                    "edges_sorted"))
def fused_ggnn_model(
    table: jnp.ndarray,
    ids: jnp.ndarray,
    senders: jnp.ndarray,
    receivers: jnp.ndarray,
    gidx: jnp.ndarray,
    mask: jnp.ndarray,
    ew: jnp.ndarray,
    eb: jnp.ndarray,
    xw: jnp.ndarray,
    xb: jnp.ndarray,
    hw: jnp.ndarray,
    hb: jnp.ndarray,
    gw: jnp.ndarray,
    gb: jnp.ndarray,
    head: tuple,
    *,
    n_steps: int,
    n_graphs: int,
    interpret: bool = False,
    edges_sorted: bool = True,
) -> jnp.ndarray:
    """Whole-model fused forward: embed → ``n_steps`` message rounds → GRU
    → attention pool → label head, ONE Pallas launch, per-graph logits out.

    ``table``: ``[n_sub·input_dim, embed]`` stacked per-subkey embedding
    tables; ``ids``: ``[n_nodes, n_sub]`` int32 ids already offset into
    their table slice (``GGNN.embed_nodes``'s fused-gather layout).
    ``senders``/``receivers``: receiver-sorted edge lists; ``gidx``/
    ``mask``: ``node_gidx``/``node_mask`` of the packed batch. ``ew..hb``:
    the conv's weights (torch r|z|n gate layout); ``gw``/``gb``: the
    attention gate's ``Dense(1)``; ``head``: tuple of ``(kernel, bias)``
    per classifier layer. Computes in f32 regardless of input dtype.
    Differentiable w.r.t. the table and every weight via a recompute
    ``custom_vjp`` over :func:`megabatch_reference`. Callers are expected
    to check :func:`fits_vmem_megabatch` and route over-plan shapes to
    :func:`megabatch_reference` directly.
    """
    return _megabatch_model(table, ids, senders, receivers, gidx, mask,
                            ew, eb, xw, xb, hw, hb, gw, gb, head,
                            n_steps, n_graphs, interpret, edges_sorted)
