"""Fixed-shape batched graph container and host-side batcher.

Replaces the reference's DGL graph batching (``dgl.batch`` collate in
``GraphDataLoader``, ``linevd/datamodule.py:110-141``, and the ``graphs.bin``
serialization of ``sastvd/scripts/dbize_graphs.py:20-33``) with an
XLA-friendly design:

- :class:`BatchedGraphs` — flat arrays with **static shapes**: every batch in a
  bucket has exactly ``max_nodes`` nodes, ``max_edges`` edges and
  ``max_graphs`` graph slots; real entries are marked by masks.
- Padding convention: the **last graph slot(s)** own all padding nodes; padding
  edges are self-loops on the last (padding) node. Segment reductions therefore
  dump padding contributions into padding slots that masks exclude — no
  device-side filtering needed.
- :func:`batch_np` — host-side (numpy) packer: concatenate graphs with node
  offsets, then pad to the bucket budget.
- :class:`GraphBatcher` — greedy packer over a dataset producing fixed-shape
  batches under (graphs, nodes, edges) budgets, with optional multi-bucket
  support to bound padding waste at a bounded number of XLA compilations.

Serialization: ``save_shards``/``load_shards`` store per-graph arrays in
``.npz`` shards (replacing DGL's ``graphs.bin``).
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, NamedTuple, Sequence

import numpy as np

__all__ = [
    "Graph",
    "BatchedGraphs",
    "compact_view",
    "view_fits",
    "batch_np",
    "GraphBatcher",
    "BucketSpec",
    "derive_buckets",
    "padding_efficiency",
    "save_shards",
    "load_shards",
    "ShardIntegrityError",
]


@dataclasses.dataclass
class Graph:
    """A single (host-side, numpy) graph.

    ``node_feats`` values are ``[n_nodes, ...]`` arrays; integer feature ids,
    labels (``_VULN``), dataflow bit-vectors etc. all live here. The dict is
    carried generically through batching/sharding — new feature families
    (e.g. the ``_DFA_{live_out,uninit,taint}`` static-analysis ids emitted
    when ``FeatureConfig.dataflow_families`` is on) need no carrier changes.
    """

    senders: np.ndarray  # [n_edges] int32, source node index
    receivers: np.ndarray  # [n_edges] int32
    node_feats: dict[str, np.ndarray]
    gid: int = -1  # dataset graph id (Big-Vul function id); host-side only

    @property
    def n_nodes(self) -> int:
        for v in self.node_feats.values():
            return int(v.shape[0])
        if self.senders.size == 0:
            return 0
        return int(max(self.senders.max(), self.receivers.max()) + 1)

    @property
    def n_edges(self) -> int:
        return int(self.senders.shape[0])

    def with_self_loops(self) -> "Graph":
        """Append one self-loop per node (parity with ``dbize_graphs.py:26``,
        which calls ``dgl.add_self_loop``); required by GGNN message passing so
        every node sees its own state."""
        n = self.n_nodes
        loop = np.arange(n, dtype=np.int32)
        return dataclasses.replace(
            self,
            senders=np.concatenate([self.senders.astype(np.int32), loop]),
            receivers=np.concatenate([self.receivers.astype(np.int32), loop]),
        )


class BatchedGraphs(NamedTuple):
    """Device-ready batch. All shapes static within a bucket.

    node_feats: dict of ``[max_nodes, ...]`` arrays.
    senders/receivers: ``[max_edges]`` int32 into the node axis, SORTED by
    receiver (``batch_np`` contract) so segment reductions over receivers
    may pass ``indices_are_sorted=True``.
    node_gidx: ``[max_nodes]`` int32 graph slot of each node.
    node_mask / edge_mask / graph_mask: bool validity masks.
    """

    node_feats: dict
    senders: np.ndarray
    receivers: np.ndarray
    node_gidx: np.ndarray
    node_mask: np.ndarray
    edge_mask: np.ndarray
    graph_mask: np.ndarray

    @property
    def max_nodes(self) -> int:
        return self.node_gidx.shape[0]

    @property
    def max_graphs(self) -> int:
        return self.graph_mask.shape[0]


def view_fits(batch: BatchedGraphs, n: int, e: int):
    """Whether the first ``n`` nodes and ``e`` edges of ``batch`` hold all its
    real ones with node ``n - 1`` left a pad (the view's sink). Numpy or traced
    masks alike: a scalar bool of the masks' own kind."""
    return (batch.node_mask.sum() <= n - 1) & (batch.edge_mask.sum() <= e)


def compact_view(batch: BatchedGraphs, n: int, e: int) -> BatchedGraphs:
    """The first ``n`` nodes and ``e`` edges of a ``batch_np`` batch as a batch
    of their own, valid wherever :func:`view_fits` holds: ``batch_np`` lays
    real nodes and real edges first, so the prefix keeps them all, and the pad
    edges it keeps, which pointed at the sink node ``max_nodes - 1``, are
    clamped onto the view's sink ``n - 1`` (a pad, above every real receiver,
    so the edges stay sorted by receiver). ``graph_mask`` is whole: the view
    pools into the same ``max_graphs`` slots. Slices and one clamp, on numpy
    arrays and inside a jitted function alike."""
    sink = n - 1
    return BatchedGraphs(
        node_feats={k: v[:n] for k, v in batch.node_feats.items()},
        senders=batch.senders[:e].clip(max=sink),
        receivers=batch.receivers[:e].clip(max=sink),
        node_gidx=batch.node_gidx[:n],
        node_mask=batch.node_mask[:n],
        edge_mask=batch.edge_mask[:e],
        graph_mask=batch.graph_mask,
    )


def batch_np(
    graphs: Sequence[Graph],
    max_graphs: int,
    max_nodes: int,
    max_edges: int,
    extra_feat_pad: dict[str, float] | None = None,
) -> BatchedGraphs:
    """Concatenate ``graphs`` and pad to the static budget (numpy, host-side).

    Requires ``sum(n_nodes) <= max_nodes - 1`` (one node reserved for edge
    padding) and ``len(graphs) <= max_graphs - 1`` (one slot reserved as the
    padding graph).
    """
    n_real = len(graphs)
    tot_nodes = sum(g.n_nodes for g in graphs)
    tot_edges = sum(g.n_edges for g in graphs)
    if n_real > max_graphs - 1:
        raise ValueError(f"{n_real} graphs > budget {max_graphs - 1}")
    if tot_nodes > max_nodes - 1:
        raise ValueError(f"{tot_nodes} nodes > budget {max_nodes - 1}")
    if tot_edges > max_edges:
        raise ValueError(f"{tot_edges} edges > budget {max_edges}")

    senders = np.full(max_edges, max_nodes - 1, dtype=np.int32)
    receivers = np.full(max_edges, max_nodes - 1, dtype=np.int32)
    node_gidx = np.full(max_nodes, max_graphs - 1, dtype=np.int32)

    node_off = 0
    edge_off = 0
    for gi, g in enumerate(graphs):
        nn, ne = g.n_nodes, g.n_edges
        senders[edge_off : edge_off + ne] = g.senders + node_off
        receivers[edge_off : edge_off + ne] = g.receivers + node_off
        node_gidx[node_off : node_off + nn] = gi
        node_off += nn
        edge_off += ne

    # Contract: edges sorted by receiver (stable). Real receivers are all
    # < max_nodes-1 (the padding sink), so padding edges stay at the end.
    # Sorting here — cheap numpy on the host, once per batch — lets every
    # device-side scatter-add take XLA's sorted-segment fast path, and the
    # model no longer pays a device-side O(E log² E) bitonic argsort once
    # per jitted forward.
    order = np.argsort(receivers, kind="stable")
    senders = senders[order]
    receivers = receivers[order]

    node_feats: dict[str, np.ndarray] = {}
    keys = graphs[0].node_feats.keys() if graphs else ()
    pad_values = extra_feat_pad or {}
    for key in keys:
        parts = [g.node_feats[key] for g in graphs]
        sample = parts[0]
        shape = (max_nodes,) + sample.shape[1:]
        out = np.full(shape, pad_values.get(key, 0), dtype=sample.dtype)
        cat = np.concatenate(parts, axis=0)
        out[: cat.shape[0]] = cat
        node_feats[key] = out

    node_mask = np.arange(max_nodes) < tot_nodes
    edge_mask = np.arange(max_edges) < tot_edges
    graph_mask = np.arange(max_graphs) < n_real
    return BatchedGraphs(
        node_feats=node_feats,
        senders=senders,
        receivers=receivers,
        node_gidx=node_gidx,
        node_mask=node_mask,
        edge_mask=edge_mask,
        graph_mask=graph_mask,
    )


@dataclasses.dataclass(frozen=True)
class BucketSpec:
    """Static padded-batch budget. One graph slot (``max_graphs - 1``) and one
    node slot (``max_nodes - 1``) are RESERVED as the padding sinks — padding
    nodes point at the sink graph, padding edges at the sink node — so a
    bucket holds at most ``max_graphs - 1`` real graphs over
    ``max_nodes - 1`` real nodes (see :func:`batch_np`). ``max_graphs`` and
    ``max_nodes`` must therefore be ≥ 2 for the bucket to hold anything."""

    max_graphs: int
    max_nodes: int
    max_edges: int

    def fits(self, n_graphs: int, n_nodes: int, n_edges: int) -> bool:
        return (
            n_graphs <= self.max_graphs - 1
            and n_nodes <= self.max_nodes - 1
            and n_edges <= self.max_edges
        )


class GraphBatcher:
    """Greedy fixed-shape packer.

    Packs graphs in the given order until the next graph would exceed the
    bucket budget, then emits a padded :class:`BatchedGraphs`. With multiple
    buckets, each emitted batch uses the smallest bucket that fits, bounding
    both padding waste and the number of distinct compiled shapes.

    This is the XLA replacement for per-epoch dynamic ``dgl.batch`` collate;
    per-epoch undersampling composes with it by re-ordering/re-selecting the
    graph list host-side each epoch (see ``data/sampler.py``).
    """

    def __init__(self, buckets: Sequence[BucketSpec], drop_oversize: bool = True,
                 collect_oversize: bool = False):
        if not buckets:
            raise ValueError("need at least one bucket")
        for b in buckets:
            if b.max_graphs < 2 or b.max_nodes < 2:
                # the padding-sink reservation makes such a bucket hold zero
                # real graphs — with drop_oversize it would silently drop ALL
                raise ValueError(
                    f"unusable bucket {b}: max_graphs and max_nodes must be "
                    "≥ 2 (one slot each is reserved as the padding sink)"
                )
        self.buckets = sorted(buckets, key=lambda b: (b.max_nodes, b.max_edges, b.max_graphs))
        self.big = self.buckets[-1]
        self.drop_oversize = drop_oversize
        self.collect_oversize = collect_oversize
        self.n_dropped = 0
        self.oversize_graphs: list[Graph] = []

    def batches(self, graphs: Sequence[Graph]) -> Iterator[BatchedGraphs]:
        # per-pass counters (batches() is re-run every epoch)
        self.n_dropped = 0
        self.oversize_graphs = []
        pending: list[Graph] = []
        nn = ne = 0
        for g in graphs:
            if not self.big.fits(1, g.n_nodes, g.n_edges):
                if self.collect_oversize:
                    # kept for the caller to rescue through a dedicated
                    # overflow bucket (trainer route) — nothing silently lost
                    self.oversize_graphs.append(g)
                    continue
                if self.drop_oversize:
                    self.n_dropped += 1
                    continue
                raise ValueError(
                    f"graph gid={g.gid} ({g.n_nodes} nodes, {g.n_edges} edges) "
                    f"exceeds the largest bucket {self.big}"
                )
            if pending and not self.big.fits(len(pending) + 1, nn + g.n_nodes, ne + g.n_edges):
                yield self._emit(pending, nn, ne)
                pending, nn, ne = [], 0, 0
            pending.append(g)
            nn += g.n_nodes
            ne += g.n_edges
        if pending:
            yield self._emit(pending, nn, ne)

    def _emit(self, pending: list[Graph], nn: int, ne: int) -> BatchedGraphs:
        bucket = next(b for b in self.buckets if b.fits(len(pending), nn, ne))
        return batch_np(pending, bucket.max_graphs, bucket.max_nodes, bucket.max_edges)


def _round_up(x: int, mult: int = 128) -> int:
    return ((int(x) + mult - 1) // mult) * mult


def derive_buckets(
    graphs: Sequence[Graph],
    batch_graphs: int,
    headroom: float = 1.08,
    sub_buckets: Sequence[float] = (0.25, 0.5),
    round_to: int = 128,
) -> list[BucketSpec]:
    """Bucket budgets sized to the corpus instead of a worst-case constant.

    The reference's DGL collate pays no padding (ragged batches); a static-
    shape TPU batch does, so budgets matter: a 40,960-node budget holding
    ~15k real nodes runs the dense GGNN matmuls ~3× oversized. This derives
    the main bucket from measured mean nodes/edges per graph
    (``batch_graphs × mean × headroom``, rounded up to ``round_to`` for MXU-
    friendly tiling) plus scaled-down sub-buckets so tail batches (end of
    epoch, node-budget-limited packs) don't pay full-size padding either.
    """
    if not graphs:
        raise ValueError("cannot derive buckets from an empty corpus")
    mean_nodes = float(np.mean([g.n_nodes for g in graphs]))
    mean_edges = float(np.mean([g.n_edges for g in graphs]))
    max_nodes_1 = max(g.n_nodes for g in graphs)
    max_edges_1 = max(g.n_edges for g in graphs)

    def spec(frac: float) -> BucketSpec:
        n_g = max(int(round(batch_graphs * frac)), 1)
        return BucketSpec(
            max_graphs=n_g + 1,
            # a bucket must hold at least the largest single graph
            max_nodes=_round_up(max(n_g * mean_nodes * headroom, max_nodes_1 + 1), round_to),
            max_edges=_round_up(max(n_g * mean_edges * headroom, max_edges_1), round_to),
        )

    buckets = [spec(f) for f in (*sub_buckets, 1.0)]
    # drop sub-buckets that collapsed into the same size as a larger one
    out: list[BucketSpec] = []
    for b in buckets:
        if not out or b != out[-1]:
            out.append(b)
    return out


def padding_efficiency(batches: Sequence[BatchedGraphs]) -> dict[str, float]:
    """Fraction of the padded budgets occupied by real entries. The node
    number is the direct multiplier on useful FLOPs in the dense GGNN path."""
    real_n = sum(int(b.node_mask.sum()) for b in batches)
    real_e = sum(int(b.edge_mask.sum()) for b in batches)
    real_g = sum(int(b.graph_mask.sum()) for b in batches)
    pad_n = sum(b.node_mask.shape[0] for b in batches)
    pad_e = sum(b.edge_mask.shape[0] for b in batches)
    pad_g = sum(b.graph_mask.shape[0] for b in batches)
    return {
        "nodes": real_n / pad_n if pad_n else 0.0,
        "edges": real_e / pad_e if pad_e else 0.0,
        "graphs": real_g / pad_g if pad_g else 0.0,
    }


class ShardIntegrityError(RuntimeError):
    """A materialised shard failed its sha256 manifest check — names the
    corrupt shard so the operator can re-materialise it, instead of a
    downstream npz/pickle decode crash pointing nowhere."""


def _sha256_file(path) -> str:
    import hashlib

    h = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 20), b""):
            h.update(block)
    return h.hexdigest()


def save_shards(graphs: Sequence[Graph], out_dir, shard_size: int = 4096) -> int:
    """Write graphs to ``shard_{i:05d}.npz`` files (replaces ``graphs.bin``)
    plus a ``manifest.json`` recording each shard's sha256 + graph count —
    :func:`load_shards` verifies the hashes before decoding anything."""
    import json
    from pathlib import Path

    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    n_shards = 0
    manifest: dict[str, dict] = {}
    for si in range(0, len(graphs), shard_size):
        chunk = graphs[si : si + shard_size]
        payload: dict[str, np.ndarray] = {
            "gids": np.array([g.gid for g in chunk], dtype=np.int64)
        }
        for i, g in enumerate(chunk):
            payload[f"s{i}"] = g.senders.astype(np.int32)
            payload[f"r{i}"] = g.receivers.astype(np.int32)
            for key, val in g.node_feats.items():
                payload[f"f{i}:{key}"] = val
        name = f"shard_{n_shards:05d}.npz"
        np.savez_compressed(out / name, **payload)
        manifest[name] = {"sha256": _sha256_file(out / name), "graphs": len(chunk)}
        n_shards += 1
    # atomic sidecar (journal protocol): a crash mid-write must not leave a
    # torn manifest that poisons every future load
    from deepdfa_tpu.resilience.journal import atomic_write_text

    atomic_write_text(
        out / "manifest.json",
        json.dumps({"schema": 1, "shards": manifest}, indent=2),
    )
    return n_shards


def load_shards(in_dir) -> list[Graph]:
    """Load materialised shards; when a ``manifest.json`` is present (every
    corpus written since the manifest landed) each shard's sha256 is
    verified FIRST — a flipped bit or truncated file raises
    :class:`ShardIntegrityError` naming the corrupt shard. Legacy dirs
    without a manifest load unverified."""
    import json
    import logging
    from pathlib import Path

    shard_files = sorted(Path(in_dir).glob("shard_*.npz"))
    manifest_file = Path(in_dir) / "manifest.json"
    if manifest_file.exists():
        entries = json.loads(manifest_file.read_text()).get("shards", {})
        on_disk = {p.name for p in shard_files}
        missing = sorted(set(entries) - on_disk)
        if missing:
            raise ShardIntegrityError(
                f"shard(s) listed in {manifest_file} but missing on disk: "
                f"{', '.join(missing)}"
            )
        for shard in shard_files:
            entry = entries.get(shard.name)
            if entry is None:
                raise ShardIntegrityError(
                    f"shard {shard.name} present on disk but not in "
                    f"{manifest_file} — stale or foreign file in the shard dir"
                )
            digest = _sha256_file(shard)
            if digest != entry["sha256"]:
                logging.getLogger(__name__).error(
                    "shard integrity failure: %s sha256 %s != recorded %s",
                    shard, digest, entry["sha256"],
                )
                raise ShardIntegrityError(
                    f"shard {shard.name} is corrupt: sha256 {digest[:12]}… does "
                    f"not match the manifest ({entry['sha256'][:12]}…) — "
                    "re-materialise the corpus"
                )

    graphs: list[Graph] = []
    for shard in shard_files:
        with np.load(shard) as z:
            gids = z["gids"]
            for i, gid in enumerate(gids):
                feats = {
                    k.split(":", 1)[1]: z[k]
                    for k in z.files
                    if k.startswith(f"f{i}:")
                }
                graphs.append(
                    Graph(
                        senders=z[f"s{i}"],
                        receivers=z[f"r{i}"],
                        node_feats=feats,
                        gid=int(gid),
                    )
                )
    return graphs
