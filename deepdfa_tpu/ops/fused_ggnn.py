"""VMEM-resident fused GatedGraphConv forward — Pallas TPU kernel.

The r03/r05 traces pin the segment-layout GGNN step as **scatter-issue-
bound** (SCALING.md "GGNN ceiling analysis"): the gather + sorted
``segment_sum`` chain runs at ~10% of HBM bandwidth and the step sits at
2.55% of nominal. The working set is tiny — node states ~3.6 MB, edge
index vectors ~0.1 MB, weights ~0.23 MB vs the v5e's 128 MiB VMEM — so
this kernel runs the ENTIRE unrolled forward (per-round edge-type linear,
edge gather, receiver-ordered accumulation, fused GRU update) with the
node-state matrix resident in VMEM across all ``n_steps`` rounds: one HBM
read of the embeddings in, one HBM write of the final node states out.
Every intermediate HBM round-trip of the per-op dispatch — and with it the
scatter-issue bottleneck — disappears; the bound becomes VMEM gather
latency (~20× HBM). This is the classic sparse-GNN-on-dense-hardware move
(arXiv:1906.11786) and the whole-propagation fusion arXiv:2512.01678 shows
dominates per-op dispatch for small-hidden GNNs.

Kernel layout (the ``ops/int8_matmul.py`` pattern): grid ``(n_steps,)`` —
on TPU the grid is executed sequentially over the last axis, so the output
block (the node states ``h``) and the ``msg``/``agg`` scratch stay resident
in VMEM across rounds; the wrapper is invoked once per graph *bucket*
(each bucket shape compiles once, exactly like the segment forward's
per-bucket jit). The matmuls (edge linear, the two fused 3-gate GRU
projections) hit the MXU; the gather/accumulate runs as an in-VMEM edge
loop over the receiver-sorted edge list. ``interpret=True`` (any non-TPU
backend) runs the same kernel under the Pallas interpreter so the CPU
suite exercises it without hardware.

Differentiable via ``custom_vjp`` with a TWO-TIER backward:

- **Pallas training kernel** (``bwd_kernel="pallas"``, auto-selected when
  :func:`fits_vmem_train` admits the bucket): one kernel launch with grid
  ``(2·n_steps,)`` — the first ``n_steps`` grid steps recompute the forward
  banking each round's pre-update node state into a VMEM history scratch,
  the second ``n_steps`` run the reverse rounds off the banked states with
  every gradient accumulator (dh, dW for all five weight matrices) resident
  in VMEM. Forward + backward is then exactly TWO launches per batch, and
  the train step (loss, grads, optimizer update, sentinel guard) lowers to
  ONE jitted dispatch around them.
- **XLA recompute fallback** (``bwd_kernel="xla"``): re-runs the unrolled
  forward from the banked inputs in plain XLA ops and reverse-
  differentiates it — always available, used when the training working set
  (history bank + gradient accumulators) exceeds the VMEM plan.

Gradient parity with the segment path holds on both tiers because the math
is identical (``tests/test_fused_ggnn.py`` / ``tests/test_fused_train.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "fused_ggnn",
    "working_set_bytes",
    "fits_vmem",
    "train_working_set_bytes",
    "fits_vmem_train",
    "edge_smem_bytes",
    "VMEM_CAP_BYTES",
    "SMEM_CAP_BYTES",
]


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _round_up(a: int, b: int) -> int:
    return _cdiv(a, b) * b


# v5e/v5p VMEM is 128 MiB per core (SCALING.md "GGNN ceiling analysis").
# The planning cap is deliberately conservative — Mosaic needs headroom for
# double-buffered DMA and register spills — and is enforced two ways: the
# Trainer routes any bucket whose working set exceeds it through the
# segment-layout fallback twin (same params), and the static guard test
# (tests/test_fused_ggnn.py) walks every bucket shape the corpus-derived
# bucketing can emit so a config change fails in CI rather than on-chip.
VMEM_BYTES = 128 * 2**20
VMEM_CAP_BYTES = 96 * 2**20
# SMEM is 1 MiB per core on the v5e (the compiler reports "Used 1.00M of
# 1.00M smem" at 2 x 512 KiB of prefetched operands plus 144 B of its own);
# the edge indices live there, so the plans cap them with a margin.
SMEM_CAP_BYTES = 960 * 2**10

# The plan's cap IS the scoped-VMEM limit handed to Mosaic: without
# ``vmem_limit_bytes`` the compiler applies its small default scoped limit
# and refuses buckets the plan admits. The grid axis is the round index —
# sequential by construction, never sharded across cores.
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary",),
    vmem_limit_bytes=VMEM_CAP_BYTES,
)


def _resident(shape) -> pl.BlockSpec:
    """Whole-array VMEM block with a constant index map. Single-buffered:
    the block index never changes across the round grid, so the default
    double buffer would only double what the working-set plans count."""
    return pl.BlockSpec(shape, lambda *_: (0,) * len(shape),
                        pipeline_mode=pl.Buffered(1),
                        memory_space=pltpu.VMEM)


def working_set_bytes(n_nodes: int, n_edges: int, width: int) -> int:
    """Conservative per-bucket VMEM working set of the fused kernel.

    Counts the resident f32 node-state blocks (``h`` in, ``h`` out, ``msg``
    and ``agg`` scratch), the GRU intermediates (two 3-gate projection
    outputs plus the r/z/n gate temps — transient, but Mosaic materialises
    vector temporaries in VMEM), the padded weight/bias blocks, and the
    edge index vectors. The indices are scalar-prefetched into SMEM (the
    edge loop addresses VMEM rows with them; :func:`edge_smem_bytes` is
    their real budget) — their sublane-padded size stays in this count as
    headroom for Mosaic's own scratch. Shapes are padded exactly as the
    wrapper pads them.
    """
    np_ = _round_up(max(n_nodes, 8), 8)
    dp = _round_up(max(width, 1), 128)
    ep = _round_up(max(n_edges, 1), 128)
    node_blocks = 4 * np_ * dp * 4            # h_in, h_out, msg, agg
    gru_temps = (2 * 3 * dp + 3 * dp) * np_ * 4   # xp, hp, r/z/n
    weights = (dp * dp + 2 * dp * 3 * dp + 7 * dp) * 4  # ew, xw, hw + biases
    edges = 2 * 8 * ep * 4                    # senders, receivers
    return node_blocks + gru_temps + weights + edges


def edge_smem_bytes(n_edges: int) -> int:
    """SMEM footprint of the scalar-prefetched sender/receiver vectors
    (1-D int32, padded exactly as the wrapper pads them)."""
    return 2 * _round_up(max(n_edges, 1), 128) * 4


def fits_vmem(n_nodes: int, n_edges: int, width: int) -> bool:
    """Whether a bucket shape is safe for the fused kernel on-chip. Buckets
    over either cap (e.g. the worst-case overflow rescue bucket) take the
    segment-layout fallback — correctness is never gated on VMEM."""
    return (working_set_bytes(n_nodes, n_edges, width) <= VMEM_CAP_BYTES
            and edge_smem_bytes(n_edges) <= SMEM_CAP_BYTES)


def train_working_set_bytes(
    n_nodes: int, n_edges: int, width: int, n_steps: int
) -> int:
    """Conservative VMEM working set of the fused TRAINING (backward)
    kernel. On top of the forward's blocks it must hold the per-round
    state history bank (``n_steps`` node blocks — the recompute forward
    banks each pre-update state so the reverse rounds read them at VMEM
    latency) and the resident gradient accumulators: dh carry, per-round
    dagg/dmsg temps, the 3-gate cotangent blocks, and one gradient block
    per weight/bias. Shapes padded exactly as the wrapper pads them."""
    np_ = _round_up(max(n_nodes, 8), 8)
    dp = _round_up(max(width, 1), 128)
    ep = _round_up(max(n_edges, 1), 128)
    node_block = np_ * dp * 4
    # h0 in, g in, dh0 out, hcur/msg/agg/dagg/dmsg scratch
    node_blocks = 8 * node_block
    hist = n_steps * node_block
    # xp/hp recompute + dxp/dhp cotangents (3-gate width) + r/z/n-style
    # vector temporaries Mosaic materialises in VMEM
    gate_blocks = (4 * 3 + 6) * node_block
    # weights AND their resident gradient accumulators
    weights = 2 * (dp * dp + 2 * dp * 3 * dp + 7 * dp) * 4
    edges = 2 * 8 * ep * 4
    return node_blocks + hist + gate_blocks + weights + edges


def fits_vmem_train(
    n_nodes: int, n_edges: int, width: int, n_steps: int
) -> bool:
    """Whether a bucket is safe for the fused TRAINING kernel (history bank
    + gradient accumulators resident). Over-plan buckets keep the fused
    forward but take the XLA recompute backward; buckets over the forward
    plan (:func:`fits_vmem`) drop to the segment twin entirely."""
    return (
        train_working_set_bytes(n_nodes, n_edges, width, n_steps)
        <= VMEM_CAP_BYTES
        and edge_smem_bytes(n_edges) <= SMEM_CAP_BYTES
    )


def _pack_gates(w: jnp.ndarray, d: int, dp: int) -> jnp.ndarray:
    """Pad a ``[d, 3d]`` fused-gate weight to ``[dp, 3dp]`` per-gate: the
    r|z|n column blocks must stay aligned to the PADDED width or the
    kernel's split at ``dp`` boundaries would mix gates."""
    w3 = w.reshape(d, 3, d)
    w3 = jnp.pad(w3, ((0, dp - d), (0, 0), (0, dp - d)))
    return w3.reshape(dp, 3 * dp)


def _pack_gate_bias(b: jnp.ndarray, d: int, dp: int) -> jnp.ndarray:
    b3 = jnp.pad(b.reshape(3, d), ((0, 0), (0, dp - d)))
    return b3.reshape(1, 3 * dp)


def _pad_conv_weights(ew, eb, xw, xb, hw, hb, d: int, dp: int):
    """The conv's six weight operands as f32 blocks padded to the lane
    tile, gates packed per gate (every kernel wrapper pads them alike)."""
    f32 = jnp.float32
    return (
        jnp.pad(ew.astype(f32), ((0, dp - d), (0, dp - d))),
        jnp.pad(eb.astype(f32), (0, dp - d)).reshape(1, dp),
        _pack_gates(xw.astype(f32), d, dp),
        _pack_gate_bias(xb.astype(f32), d, dp),
        _pack_gates(hw.astype(f32), d, dp),
        _pack_gate_bias(hb.astype(f32), d, dp),
    )


def _kernel(snd_ref, rcv_ref, h0_ref, ew_ref, eb_ref, xw_ref, xb_ref,
            hw_ref, hb_ref, out_ref, msg_ref, agg_ref, *, n_edges: int,
            width: int):
    """One message round. Grid axis 0 is the round index: TPU executes the
    last grid axis sequentially, so ``out_ref`` (the node states) and the
    scratch persist in VMEM across all rounds — the whole unrolled forward
    touches HBM exactly twice (embeddings in, final states out)."""
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _load():
        out_ref[:] = h0_ref[:]

    h = out_ref[:]
    # edge-type linear on the MXU (n_etypes=1 commutes it to per-node,
    # exactly as the segment forward does)
    msg_ref[:] = (
        jnp.dot(h, ew_ref[:], preferred_element_type=jnp.float32) + eb_ref[:]
    )
    agg_ref[:] = jnp.zeros_like(agg_ref)

    # Receiver-ordered accumulation in VMEM: the edge list arrives sorted
    # by receiver (the ``batch_np`` contract), so this loop IS the sorted-
    # segment sum — at VMEM latency instead of the HBM scatter path.
    def edge_body(e, carry):
        s = snd_ref[e]
        r = rcv_ref[e]
        agg_ref[pl.ds(r, 1), :] += msg_ref[pl.ds(s, 1), :]
        return carry

    jax.lax.fori_loop(0, n_edges, edge_body, 0)

    # fused GRU update (torch r|z|n gate layout, parity with models.GRUCell)
    xp = jnp.dot(agg_ref[:], xw_ref[:], preferred_element_type=jnp.float32) + xb_ref[:]
    hp = jnp.dot(h, hw_ref[:], preferred_element_type=jnp.float32) + hb_ref[:]
    d = width
    r = jax.nn.sigmoid(xp[:, :d] + hp[:, :d])
    z = jax.nn.sigmoid(xp[:, d:2 * d] + hp[:, d:2 * d])
    n = jnp.tanh(xp[:, 2 * d:] + r * hp[:, 2 * d:])
    out_ref[:] = (1.0 - z) * n + z * h


def _unpack_gates(wp: jnp.ndarray, d: int, dp: int) -> jnp.ndarray:
    """Inverse of :func:`_pack_gates`: slice a ``[dp, 3dp]`` per-gate padded
    block back to the ``[d, 3d]`` fused layout."""
    return wp.reshape(dp, 3, dp)[:d, :, :d].reshape(d, 3 * d)


def _unpack_gate_bias(bp: jnp.ndarray, d: int, dp: int) -> jnp.ndarray:
    return bp.reshape(3, dp)[:, :d].reshape(3 * d)


def _train_kernel(snd_ref, rcv_ref, h0_ref, ew_ref, eb_ref, xw_ref, xb_ref,
                  hw_ref, hb_ref, g_ref,
                  dh0_ref, dew_ref, deb_ref, dxw_ref, dxb_ref, dhw_ref,
                  dhb_ref, hist_ref, hcur_ref, msg_ref, agg_ref, dagg_ref,
                  dmsg_ref, *, n_edges: int, width: int, n_steps: int):
    """Fused training backward: grid ``(2·n_steps,)``, executed sequentially
    on TPU so every output/scratch block stays VMEM-resident across the
    whole recompute-forward + reverse sweep.

    Steps ``0..n_steps-1`` recompute the forward, banking each round's
    PRE-update node state into ``hist``; steps ``n_steps..2·n_steps-1`` run
    round ``t = 2·n_steps-1-step`` of reverse-mode accumulation: gates are
    recomputed from the banked state (cheaper than banking them — one
    extra pair of matmuls vs six more resident 3-gate blocks) and the
    cotangent chain mirrors the forward exactly:

        h' = (1-z)·n + z·h  ⇒  dz = g·(h-n); dn = g·(1-z); dh += g·z
        n = tanh(xn + r·hn) ⇒  dpre_n = dn·(1-n²); dr = dpre_n·hn
        r, z = σ(·)         ⇒  dpre_r = dr·r·(1-r); dpre_z = dz·z·(1-z)
        agg[r] += msg[s]    ⇒  dmsg[s] += dagg[r]  (transpose edge loop)

    ``dh0_ref`` doubles as the running dh carry — after the last reverse
    round it IS dL/dh0."""
    step = pl.program_id(0)
    d = width
    f32 = jnp.float32

    @pl.when(step == 0)
    def _load():
        hcur_ref[:] = h0_ref[:]

    @pl.when(step < n_steps)
    def _forward_bank():
        t = step
        h = hcur_ref[:]
        hist_ref[pl.ds(t, 1)] = h[None]
        msg_ref[:] = jnp.dot(h, ew_ref[:], preferred_element_type=f32) + eb_ref[:]
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
    def _init_grads():
        dh0_ref[:] = g_ref[:]
        dew_ref[:] = jnp.zeros_like(dew_ref)
        deb_ref[:] = jnp.zeros_like(deb_ref)
        dxw_ref[:] = jnp.zeros_like(dxw_ref)
        dxb_ref[:] = jnp.zeros_like(dxb_ref)
        dhw_ref[:] = jnp.zeros_like(dhw_ref)
        dhb_ref[:] = jnp.zeros_like(dhb_ref)

    @pl.when(step >= n_steps)
    def _reverse():
        t = 2 * n_steps - 1 - step
        h = hist_ref[pl.ds(t, 1)][0]
        # recompute round t's intermediates from the banked state
        msg_ref[:] = jnp.dot(h, ew_ref[:], preferred_element_type=f32) + eb_ref[:]
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
        hn = hp[:, 2 * d:]
        n = jnp.tanh(xp[:, 2 * d:] + r * hn)

        g = dh0_ref[:]
        dz = g * (h - n)
        dn = g * (1.0 - z)
        dpre_n = dn * (1.0 - n * n)
        dr = dpre_n * hn
        dpre_r = dr * r * (1.0 - r)
        dpre_z = dz * z * (1.0 - z)
        dxp = jnp.concatenate([dpre_r, dpre_z, dpre_n], axis=1)
        dhp = jnp.concatenate([dpre_r, dpre_z, dpre_n * r], axis=1)

        contract_last = (((1,), (1,)), ((), ()))   # a @ b.T
        contract_rows = (((0,), (0,)), ((), ()))   # a.T @ b
        # x-projection: xp = agg @ xw + xb
        dagg_ref[:] = jax.lax.dot_general(
            dxp, xw_ref[:], contract_last, preferred_element_type=f32)
        dxw_ref[:] += jax.lax.dot_general(
            agg_ref[:], dxp, contract_rows, preferred_element_type=f32)
        dxb_ref[:] += jnp.sum(dxp, axis=0, keepdims=True)
        # h-projection: hp = h @ hw + hb (plus the direct z·h path)
        dh = g * z + jax.lax.dot_general(
            dhp, hw_ref[:], contract_last, preferred_element_type=f32)
        dhw_ref[:] += jax.lax.dot_general(
            h, dhp, contract_rows, preferred_element_type=f32)
        dhb_ref[:] += jnp.sum(dhp, axis=0, keepdims=True)
        # transpose of the receiver-ordered accumulation
        dmsg_ref[:] = jnp.zeros_like(dmsg_ref)

        def edge_body_t(e, carry):
            s = snd_ref[e]
            r = rcv_ref[e]
            dmsg_ref[pl.ds(s, 1), :] += dagg_ref[pl.ds(r, 1), :]
            return carry

        jax.lax.fori_loop(0, n_edges, edge_body_t, 0)
        # edge linear: msg = h @ ew + eb
        dh = dh + jax.lax.dot_general(
            dmsg_ref[:], ew_ref[:], contract_last, preferred_element_type=f32)
        dew_ref[:] += jax.lax.dot_general(
            h, dmsg_ref[:], contract_rows, preferred_element_type=f32)
        deb_ref[:] += jnp.sum(dmsg_ref[:], axis=0, keepdims=True)
        dh0_ref[:] = dh


def _pallas_train_bwd(h0, senders, receivers, ew, eb, xw, xb, hw, hb, g,
                      n_steps: int, interpret: bool):
    """Dispatch the fused training kernel; returns UNPADDED cotangents
    ``(dh0, dew, deb, dxw, dxb, dhw, dhb)`` in f32."""
    n, d = h0.shape
    e = senders.shape[0]
    np_ = _round_up(max(n, 8), 8)
    dp = _round_up(max(d, 1), 128)
    ep = _round_up(max(e, 1), 128)

    h0p = jnp.pad(h0.astype(jnp.float32), ((0, np_ - n), (0, dp - d)))
    gp = jnp.pad(g.astype(jnp.float32), ((0, np_ - n), (0, dp - d)))
    sndp = jnp.pad(senders.astype(jnp.int32), (0, ep - e))
    rcvp = jnp.pad(receivers.astype(jnp.int32), (0, ep - e))
    ewp, ebp, xwp, xbp, hwp, hbp = _pad_conv_weights(
        ew, eb, xw, xb, hw, hb, d, dp)

    outs = pl.pallas_call(
        functools.partial(_train_kernel, n_edges=e, width=dp, n_steps=n_steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,      # senders, receivers → SMEM
            grid=(2 * n_steps,),
            in_specs=[
                _resident((np_, dp)),           # h0
                _resident((dp, dp)),            # edge_linear kernel
                _resident((1, dp)),             # edge_linear bias
                _resident((dp, 3 * dp)),        # gru x_proj kernel
                _resident((1, 3 * dp)),         # gru x_proj bias
                _resident((dp, 3 * dp)),        # gru h_proj kernel
                _resident((1, 3 * dp)),         # gru h_proj bias
                _resident((np_, dp)),           # incoming cotangent g
            ],
            out_specs=[
                _resident((np_, dp)),           # dh0 (doubles as the dh carry)
                _resident((dp, dp)),            # dew
                _resident((1, dp)),             # deb
                _resident((dp, 3 * dp)),        # dxw
                _resident((1, 3 * dp)),         # dxb
                _resident((dp, 3 * dp)),        # dhw
                _resident((1, 3 * dp)),         # dhb
            ],
            scratch_shapes=[
                pltpu.VMEM((n_steps, np_, dp), jnp.float32),   # hist
                pltpu.VMEM((np_, dp), jnp.float32),            # hcur
                pltpu.VMEM((np_, dp), jnp.float32),            # msg
                pltpu.VMEM((np_, dp), jnp.float32),            # agg
                pltpu.VMEM((np_, dp), jnp.float32),            # dagg
                pltpu.VMEM((np_, dp), jnp.float32),            # dmsg
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((np_, dp), jnp.float32),
            jax.ShapeDtypeStruct((dp, dp), jnp.float32),
            jax.ShapeDtypeStruct((1, dp), jnp.float32),
            jax.ShapeDtypeStruct((dp, 3 * dp), jnp.float32),
            jax.ShapeDtypeStruct((1, 3 * dp), jnp.float32),
            jax.ShapeDtypeStruct((dp, 3 * dp), jnp.float32),
            jax.ShapeDtypeStruct((1, 3 * dp), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(sndp, rcvp, h0p, ewp, ebp, xwp, xbp, hwp, hbp, gp)
    dh0p, dewp, debp, dxwp, dxbp, dhwp, dhbp = outs
    return (
        dh0p[:n, :d],
        dewp[:d, :d],
        debp[0, :d],
        _unpack_gates(dxwp, d, dp),
        _unpack_gate_bias(dxbp, d, dp),
        _unpack_gates(dhwp, d, dp),
        _unpack_gate_bias(dhbp, d, dp),
    )


def _unrolled_reference(h0, senders, receivers, ew, eb, xw, xb, hw, hb,
                        n_steps: int, edges_sorted: bool):
    """The same math in plain XLA ops — the recompute the backward
    differentiates. Bitwise-equivalent reductions: both paths accumulate
    edges in list order per receiver."""
    n_nodes = h0.shape[0]
    h = h0
    for _ in range(n_steps):
        msg = h @ ew + eb
        agg = jax.ops.segment_sum(
            jnp.take(msg, senders, axis=0), receivers,
            num_segments=n_nodes, indices_are_sorted=edges_sorted,
        )
        xp = agg @ xw + xb
        hp = h @ hw + hb
        xr, xz, xn = jnp.split(xp, 3, axis=-1)
        hr, hz, hn = jnp.split(hp, 3, axis=-1)
        r = jax.nn.sigmoid(xr + hr)
        z = jax.nn.sigmoid(xz + hz)
        n = jnp.tanh(xn + r * hn)
        h = (1.0 - z) * n + z * h
    return h


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11, 12))
def _fused_ggnn(h0, senders, receivers, ew, eb, xw, xb, hw, hb,
                n_steps: int, interpret: bool, edges_sorted: bool,
                bwd_kernel: str):
    n, d = h0.shape
    e = senders.shape[0]
    if n_steps == 0:
        return h0.astype(jnp.float32)
    np_ = _round_up(max(n, 8), 8)
    dp = _round_up(max(d, 1), 128)
    ep = _round_up(max(e, 1), 128)

    h0p = jnp.pad(h0.astype(jnp.float32), ((0, np_ - n), (0, dp - d)))
    sndp = jnp.pad(senders.astype(jnp.int32), (0, ep - e))
    rcvp = jnp.pad(receivers.astype(jnp.int32), (0, ep - e))
    ewp, ebp, xwp, xbp, hwp, hbp = _pad_conv_weights(
        ew, eb, xw, xb, hw, hb, d, dp)

    out = pl.pallas_call(
        functools.partial(_kernel, n_edges=e, width=dp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,      # senders, receivers → SMEM
            grid=(n_steps,),
            in_specs=[
                _resident((np_, dp)),           # h0
                _resident((dp, dp)),            # edge_linear kernel
                _resident((1, dp)),             # edge_linear bias
                _resident((dp, 3 * dp)),        # gru x_proj kernel
                _resident((1, 3 * dp)),         # gru x_proj bias
                _resident((dp, 3 * dp)),        # gru h_proj kernel
                _resident((1, 3 * dp)),         # gru h_proj bias
            ],
            out_specs=_resident((np_, dp)),
            scratch_shapes=[
                pltpu.VMEM((np_, dp), jnp.float32),   # msg
                pltpu.VMEM((np_, dp), jnp.float32),   # agg
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((np_, dp), jnp.float32),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(sndp, rcvp, h0p, ewp, ebp, xwp, xbp, hwp, hbp)
    return out[:n, :d]


def _fused_ggnn_fwd(h0, senders, receivers, ew, eb, xw, xb, hw, hb,
                    n_steps, interpret, edges_sorted, bwd_kernel):
    out = _fused_ggnn(h0, senders, receivers, ew, eb, xw, xb, hw, hb,
                      n_steps, interpret, edges_sorted, bwd_kernel)
    # recompute-based backward: bank the (tiny) inputs, not per-round states
    return out, (h0, senders, receivers, ew, eb, xw, xb, hw, hb)


def _fused_ggnn_bwd(n_steps, interpret, edges_sorted, bwd_kernel, res, g):
    h0, senders, receivers, ew, eb, xw, xb, hw, hb = res
    n, d = h0.shape
    e = senders.shape[0]
    if bwd_kernel not in ("auto", "pallas", "xla"):
        raise ValueError(f"bwd_kernel must be auto|pallas|xla, got {bwd_kernel!r}")
    use_pallas = n_steps > 0 and (
        bwd_kernel == "pallas"
        or (bwd_kernel == "auto" and fits_vmem_train(n, e, d, n_steps)))
    if use_pallas:
        dh0, dew, deb, dxw, dxb, dhw, dhb = _pallas_train_bwd(
            h0, senders, receivers, ew, eb, xw, xb, hw, hb, g,
            n_steps, interpret)
    else:
        def ref(h0_, ew_, eb_, xw_, xb_, hw_, hb_):
            return _unrolled_reference(
                h0_.astype(jnp.float32), senders, receivers,
                ew_.astype(jnp.float32), eb_.astype(jnp.float32),
                xw_.astype(jnp.float32), xb_.astype(jnp.float32),
                hw_.astype(jnp.float32), hb_.astype(jnp.float32),
                n_steps, edges_sorted,
            )

        _, vjp = jax.vjp(ref, h0, ew, eb, xw, xb, hw, hb)
        dh0, dew, deb, dxw, dxb, dhw, dhb = vjp(g.astype(jnp.float32))
    # integer primals take float0 cotangents (JAX's tangent space for ints)
    dsnd = np.zeros(senders.shape, jax.dtypes.float0)
    drcv = np.zeros(receivers.shape, jax.dtypes.float0)
    return (dh0.astype(h0.dtype), dsnd, drcv, dew.astype(ew.dtype),
            deb.astype(eb.dtype), dxw.astype(xw.dtype), dxb.astype(xb.dtype),
            dhw.astype(hw.dtype), dhb.astype(hb.dtype))


_fused_ggnn.defvjp(_fused_ggnn_fwd, _fused_ggnn_bwd)


@functools.partial(jax.jit,
                   static_argnames=("n_steps", "interpret", "edges_sorted",
                                    "bwd_kernel"))
def fused_ggnn(
    h0: jnp.ndarray,
    senders: jnp.ndarray,
    receivers: jnp.ndarray,
    ew: jnp.ndarray,
    eb: jnp.ndarray,
    xw: jnp.ndarray,
    xb: jnp.ndarray,
    hw: jnp.ndarray,
    hb: jnp.ndarray,
    *,
    n_steps: int,
    interpret: bool = False,
    edges_sorted: bool = True,
    bwd_kernel: str = "auto",
) -> jnp.ndarray:
    """``n_steps`` rounds of (edge linear → gather(senders) →
    receiver-ordered sum → GRU) with ``h`` VMEM-resident throughout.

    ``h0``: ``[n_nodes, width]`` node embeddings (already padded to the
    conv width). ``senders``/``receivers``: ``[n_edges]`` int32, sorted by
    receiver (the ``batch_np`` contract — required only by the backward's
    sorted segment sum; pass ``edges_sorted=False`` for hand-built lists).
    ``ew``/``eb``: edge_linear kernel/bias; ``xw``/``xb``/``hw``/``hb``:
    the fused 3-gate GRU projections (torch r|z|n layout, exactly the
    ``models.GRUCell`` parameter tree). Computes in f32 regardless of input
    dtype (the VMEM-resident state is the accuracy-critical accumulator).
    ``interpret=True`` runs the same kernel under the Pallas interpreter
    (CPU tests). Differentiable w.r.t. ``h0`` and all weights via a
    recompute-based ``custom_vjp``; ``bwd_kernel`` selects the backward
    tier — ``"pallas"`` forces the fused training kernel, ``"xla"`` the
    plain recompute, ``"auto"`` (default) picks Pallas exactly when
    :func:`fits_vmem_train` admits the bucket.
    """
    return _fused_ggnn(h0, senders, receivers, ew, eb, xw, xb, hw, hb,
                       n_steps, interpret, edges_sorted, bwd_kernel)
