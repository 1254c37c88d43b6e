"""ScoringEngine — warm per-bucket compiled scorers for the online path.

The compiled-shape discipline that rules training rules serving too: XLA
programs are specialized to static shapes, so the engine owns a small
ladder of :class:`~deepdfa_tpu.data.graphs.BucketSpec` budgets (size
classes per *graph*, batch budgets per *bucket*) and keeps one compiled
callable warm per bucket. Requests are routed to the smallest size class
that fits their graph (`assign_bucket`), the batcher packs per class, and
`score` pads + dispatches — after the first `warmup()` no request ever
pays a compile.

Two constructors, one contract:

- :meth:`from_checkpoint` — live model + restored params through
  :func:`deepdfa_tpu.predict.make_scorer` (jit; any bucket ladder);
- :meth:`from_artifact` — a pre-exported StableHLO artifact
  (:mod:`deepdfa_tpu.serving`), whose ONE baked shape becomes the only
  bucket; node-label artifacts are reduced to function scores host-side.

Fleet extensions (the distributed-serving layer):

- ``mesh=`` on :meth:`from_model` replicates the engine across every
  device of a ``dp`` mesh (the :mod:`deepdfa_tpu.parallel.dp` shard-map
  machinery): :meth:`score_groups` stacks up to ``n_replicas`` padded
  batches on a leading device axis and scores them in ONE dispatch, one
  batch per device. The micro-batcher packs across replicas.
- :meth:`warmup` takes a :class:`~deepdfa_tpu.serve.warmstore.WarmStore`:
  a miss compiles as before and EXPORTS the bucket's program
  (StableHLO, content-addressed on vocab hash + model rev + bucket
  shape); a hit loads the serialized program instead of re-tracing —
  a joining replica warms its whole ladder with zero cold compiles.
  ``warmup`` returns a report (hits/misses/compile-seconds-saved) and
  journals it when given a journal.

`score` is where the ``serve.engine_raises`` fault point lives: an
injected (or real) engine failure must surface as a per-request error in
the batcher, never as a dead server. All dispatch entry points serialize
on one engine lock — concurrent ``submit()`` callers in latency mode
must never interleave their donated buffers.
"""

from __future__ import annotations

import dataclasses
import hashlib
import threading
import time
import warnings
from pathlib import Path

import numpy as np

from deepdfa_tpu.data.graphs import BucketSpec, Graph, _round_up, batch_np
from deepdfa_tpu.resilience import faults

__all__ = ["OversizeGraphError", "ServeBucket", "serve_buckets",
           "mega_bucket", "ScoringEngine", "PendingScore"]


class OversizeGraphError(ValueError):
    """The function's graph exceeds every serving bucket — a per-request
    413, not a reason to grow the compiled-shape ladder at runtime."""


@dataclasses.dataclass(frozen=True)
class ServeBucket:
    """A size class: graphs with ``n_nodes <= graph_nodes`` (and edges
    within the per-graph share) route here; ``spec`` is the padded batch
    budget the bucket's compiled callable is specialized to."""

    spec: BucketSpec
    graph_nodes: int

    @property
    def capacity(self) -> int:
        """Real-graph slots (one BucketSpec slot is the padding sink)."""
        return self.spec.max_graphs - 1

    def admits(self, g: Graph) -> bool:
        return (g.n_nodes <= self.graph_nodes
                and g.n_edges <= 4 * self.graph_nodes
                and self.spec.fits(1, g.n_nodes, g.n_edges))


def serve_buckets(max_batch: int) -> tuple[ServeBucket, ...]:
    """The default ladder: small CFGs (DeepDFA's regime, ~50 nodes) batch
    ``max_batch``-wide; mid-size functions batch narrower; huge ones go
    one-per-batch. Three compiled shapes total — bounded compile cost,
    bounded padding waste."""
    ladder = ((126, max_batch), (1022, max(1, max_batch // 4)), (4094, 1))
    out = []
    for per_graph, gcap in ladder:
        nn = _round_up(gcap * per_graph + 2)
        out.append(ServeBucket(
            spec=BucketSpec(gcap + 1, nn, 4 * nn), graph_nodes=per_graph))
    return tuple(out)


def mega_bucket(max_batch: int, graph_nodes: int = 1022) -> ServeBucket:
    """The cross-bucket megabatch budget: ONE compiled shape wide enough
    to absorb a whole mixed-size request window (small CFGs *and* mid-size
    functions together), so :meth:`ScoringEngine.score_packed` replaces
    the per-size-class ladder walk with a single dispatch. Node/edge
    budgets cover ``2 * max_batch`` DeepDFA-regime graphs plus one
    ``graph_nodes``-sized straggler — graphs over the budget still route
    through the ladder per class."""
    gcap = 2 * max(1, int(max_batch))
    nn_ = _round_up(gcap * 126 + graph_nodes + 2)
    return ServeBucket(spec=BucketSpec(gcap + 1, nn_, 4 * nn_),
                       graph_nodes=graph_nodes)


def _calibration_graphs(feat_keys, buckets, n_per_bucket: int = 4,
                        seed: int = 0):
    """Synthesized int8-gate inputs when the caller has no realworld
    fixtures handy: a few random graphs per bucket size class (feature ids
    in {0, 1} — valid rows in every embedding table). Deterministic
    (seeded) so the gate verdict is reproducible across engine builds."""
    rng = np.random.default_rng(seed)
    out = []
    for b in buckets:
        cap = min(b.graph_nodes, 48)
        for _ in range(n_per_bucket):
            n = int(rng.integers(max(2, cap // 2), cap + 1))
            feats = {k: rng.integers(0, 2, size=n).astype(np.int32)
                     for k in feat_keys}
            out.append(Graph(
                senders=rng.integers(0, n, size=2 * n).astype(np.int32),
                receivers=rng.integers(0, n, size=2 * n).astype(np.int32),
                node_feats=feats).with_self_loops())
    return out


def params_content_hash(params) -> str:
    """Model revision: a content address of the full parameter tree
    (structure + dtypes + bytes). Two engines share warm-store keys
    exactly when they serve the same weights."""
    import jax

    leaves, treedef = jax.tree.flatten(params)
    h = hashlib.sha256(str(treedef).encode())
    for leaf in leaves:
        arr = np.asarray(leaf)
        h.update(f"{arr.dtype}{arr.shape}".encode())
        h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()[:16]


class PendingScore:
    """Handle returned by :meth:`ScoringEngine.submit` — the scores stay
    device-resident (no host sync at dispatch); :meth:`result` is the one
    blocking read."""

    __slots__ = ("_dev", "_n")

    def __init__(self, dev, n: int):
        self._dev = dev
        self._n = n

    def result(self) -> np.ndarray:
        return np.asarray(self._dev, np.float32)[: self._n]


class ScoringEngine:
    """``score(graphs, bucket) -> fn_prob[len(graphs)]`` over a fixed
    bucket ladder. ``score_fn`` maps a padded ``BatchedGraphs`` to
    per-graph probabilities ``[max_graphs]`` (already sigmoid'd).

    ``device_fn`` (optional — the live-model constructors set it): a jitted
    ``device batch -> device probs`` callable whose batch argument is
    DONATED, enabling ``latency_mode`` — :meth:`submit` dispatches without
    any host sync and hands back a :class:`PendingScore`; the input buffers
    are consumed by the dispatch (donation) so a submitted batch is never
    reused host-side. ``precision`` records which weight path the engine
    serves (``f32`` or ``int8``); ``int8_score_delta`` the measured
    calibration-batch gate value when int8 was requested.

    ``stacked_fn`` (mesh-replicated engines): maps a ``[n_replicas, ...]``
    stacked batch pytree to ``[n_replicas, max_graphs]`` probabilities —
    one engine replica per device, one dispatch for the whole stack.
    ``export_fn`` (live single-replica engines): ``bucket -> (bytes,
    export_seconds)`` serializing the bucket's compiled program for the
    warm store. ``model_rev`` is the parameter content hash that keys it.

    Every dispatch path holds the engine lock: the donated-buffer submit
    sequence (pad → upload → launch) is a critical section — two threads
    interleaving it could hand one thread's donated buffers to the
    other's dispatch.
    """

    def __init__(self, score_fn, buckets, label_style: str = "graph",
                 feat_keys=(), vocab_hash: str | None = None,
                 device_fn=None, latency_mode: bool = False,
                 precision: str = "f32",
                 int8_score_delta: float | None = None,
                 stacked_fn=None, n_replicas: int = 1,
                 model_rev: str | None = None, export_fn=None,
                 mega: ServeBucket | None = None, hier_factory=None):
        if not buckets:
            raise ValueError("need at least one serving bucket")
        if score_fn is None and stacked_fn is None:
            raise ValueError("need a score_fn (or a stacked_fn for "
                             "mesh-replicated engines)")
        if n_replicas < 1:
            raise ValueError("n_replicas must be >= 1")
        self._score_fn = score_fn
        self._device_fn = device_fn
        self._stacked_fn = stacked_fn
        self._export_fn = export_fn
        self.n_replicas = int(n_replicas)
        self.model_rev = model_rev
        if latency_mode and device_fn is None:
            warnings.warn(
                "latency_mode requires a jit-safe device_fn (live-model "
                "engines only — StableHLO artifact reductions run host-side); "
                "serving in synchronous mode", stacklevel=2)
            latency_mode = False
        self.latency_mode = latency_mode
        self.precision = precision
        self.int8_score_delta = int8_score_delta
        self.buckets = tuple(sorted(
            buckets, key=lambda b: (b.graph_nodes, b.spec.max_graphs)))
        self.label_style = label_style
        self.feat_keys = tuple(feat_keys)
        self.vocab_hash = vocab_hash
        self.mega_bucket = mega
        # packed-dispatch efficiency of the last score_packed call (the
        # nodes/edges/graphs fractions the /metrics padding gauges track)
        self.last_padding_efficiency: dict[str, float] | None = None
        self.n_dispatches = 0
        self.warm_buckets: list[int] = []
        self.last_warmup_report: dict | None = None
        self._bucket_fns: dict[ServeBucket, object] = {}
        # whole-unit hierarchical scoring (models/ggnn_hier.py): live
        # megabatch-compatible engines get a lazy factory; the scorer is
        # built on first score_unit so ladder-only serving pays nothing
        self._hier_factory = hier_factory
        self._hier = None
        self._lock = threading.RLock()
        # attachment point set by the server: every dispatch records its
        # bucket + real-graph count into the crash flight recorder
        self.flight = None

    def _record_dispatch(self, kind: str, bucket, n_graphs: int) -> None:
        if self.flight is not None:  # record() never raises (invariant 14)
            self.flight.record(kind, bucket=bucket.graph_nodes,
                               n_graphs=n_graphs,
                               dispatch=self.n_dispatches)

    # -- routing ------------------------------------------------------------

    def assign_bucket(self, g: Graph) -> ServeBucket:
        for b in self.buckets:
            if b.admits(g):
                return b
        raise OversizeGraphError(
            f"graph with {g.n_nodes} nodes / {g.n_edges} edges exceeds the "
            f"largest serving bucket "
            f"(graph_nodes={self.buckets[-1].graph_nodes})")

    # -- scoring ------------------------------------------------------------

    def _padded_batch(self, graphs, bucket: ServeBucket, feat_only=False):
        batch = batch_np(graphs, bucket.spec.max_graphs,
                         bucket.spec.max_nodes, bucket.spec.max_edges)
        if feat_only:
            # an EMPTY group (a replica slot with no requests this window)
            # batches to no feature columns at all — synthesize all-padding
            # ones so every replica's leaf structure matches for stacking
            zeros = np.zeros(bucket.spec.max_nodes, np.int32)
            batch = batch._replace(node_feats={
                k: batch.node_feats.get(k, zeros) for k in self.feat_keys})
        return batch

    def score(self, graphs, bucket: ServeBucket) -> np.ndarray:
        """Pad ``graphs`` (all pre-routed to ``bucket``) and dispatch one
        compiled call; returns the real graphs' probabilities. In latency
        mode this is submit + blocking read — same semantics, one sync."""
        if self.latency_mode:
            return self.submit(graphs, bucket).result()
        if self._stacked_fn is not None:
            return self.score_groups([graphs], bucket)[0]
        faults.raise_if("serve.engine_raises")
        graphs = list(graphs)
        with self._lock:
            batch = self._padded_batch(graphs, bucket)
            fn = self._bucket_fns.get(bucket, self._score_fn)
            probs = np.asarray(fn(batch), np.float32)
            self.n_dispatches += 1
        self._record_dispatch("engine.dispatch", bucket, len(graphs))
        return probs[: len(graphs)]

    def score_groups(self, groups, bucket: ServeBucket) -> list[np.ndarray]:
        """Score up to ``n_replicas`` request groups in ONE dispatch.

        Mesh-replicated engines stack one padded batch per replica on a
        leading device axis (missing replica slots get an all-padding
        batch) and shard-map the stack across the mesh; single-replica
        engines fall back to one :meth:`score` per group. Returns one
        probability array per input group, in order."""
        groups = [list(g) for g in groups]
        if self._stacked_fn is None:
            return [self.score(g, bucket) for g in groups]
        if len(groups) > self.n_replicas:
            raise ValueError(
                f"{len(groups)} groups > {self.n_replicas} replicas — the "
                "batcher must chunk windows to the replica count")
        faults.raise_if("serve.engine_raises")
        with self._lock:
            padded = groups + [[] for _ in range(self.n_replicas - len(groups))]
            batches = [self._padded_batch(g, bucket, feat_only=True)
                       for g in padded]
            import jax

            stacked = jax.tree.map(lambda *xs: np.stack(xs, axis=0), *batches)
            probs = np.asarray(self._stacked_fn(stacked), np.float32)
            self.n_dispatches += 1
        self._record_dispatch("engine.dispatch_stacked", bucket,
                              sum(len(g) for g in groups))
        return [probs[i, : len(g)] for i, g in enumerate(groups)]

    def score_packed(self, graphs) -> np.ndarray:
        """Score a mixed-size request set through the megabatch bucket:
        first-fit-decreasing pack the whole set into as few mega-shaped
        batches as the node/edge/graph budgets allow and dispatch each —
        one dispatch where the per-size-class ladder would walk several.
        Graphs over the mega budget route through the ladder per graph
        (:meth:`assign_bucket` semantics, including
        :class:`OversizeGraphError`). Returns probabilities in input
        order; records the packed batches' padding efficiency in
        ``last_padding_efficiency``."""
        if self.mega_bucket is None:
            raise RuntimeError(
                "score_packed needs a megabatch engine — construct with "
                "from_model(..., megabatch=True) or pass mega=")
        graphs = list(graphs)
        if not graphs:
            return np.zeros(0, np.float32)
        spec = self.mega_bucket.spec
        cap = self.mega_bucket.capacity
        order = sorted(range(len(graphs)),
                       key=lambda i: (-graphs[i].n_nodes,
                                      -graphs[i].n_edges, i))
        bins: list[list[int]] = []
        loads: list[list[int]] = []  # [node-sum, edge-sum] per bin
        overflow: list[int] = []
        for i in order:
            g = graphs[i]
            if g.n_nodes > spec.max_nodes - 1 or g.n_edges > spec.max_edges:
                overflow.append(i)
                continue
            for b, load in zip(bins, loads):
                if (len(b) < cap
                        and load[0] + g.n_nodes <= spec.max_nodes - 1
                        and load[1] + g.n_edges <= spec.max_edges):
                    b.append(i)
                    load[0] += g.n_nodes
                    load[1] += g.n_edges
                    break
            else:
                bins.append([i])
                loads.append([g.n_nodes, g.n_edges])
        out = np.zeros(len(graphs), np.float32)
        for b in bins:
            out[np.asarray(b)] = self.score([graphs[i] for i in b],
                                            self.mega_bucket)
        for i in overflow:
            out[i] = self.score([graphs[i]], self.assign_bucket(graphs[i]))[0]
        if bins:
            real_n = sum(load[0] for load in loads)
            real_e = sum(load[1] for load in loads)
            self.last_padding_efficiency = {
                "nodes": real_n / (len(bins) * spec.max_nodes),
                "edges": real_e / (len(bins) * spec.max_edges),
                "graphs": sum(len(b) for b in bins)
                / (len(bins) * spec.max_graphs),
            }
        return out

    @property
    def hier(self):
        """The lazy :class:`~deepdfa_tpu.models.ggnn_hier.HierScorer` —
        live megabatch-compatible engines only. Attach an embedding cache
        via ``engine.hier.cache = FunctionEmbeddingCache(...)``."""
        with self._lock:
            if self._hier is None:
                if self._hier_factory is None:
                    raise RuntimeError(
                        "score_unit needs a live megabatch-compatible "
                        "engine (graph labels, concat-subkey embeddings) — "
                        "artifact engines and excluded model variants have "
                        "no hierarchical path")
                self._hier = self._hier_factory()
            return self._hier

    def score_unit(self, functions, supergraph) -> dict:
        """Score a merged multi-function unit as ONE request through the
        hierarchical two-level path: per-function level-1 embeddings off
        the fused megabatch kernels (cache-fronted), composed over the
        call graph into a unit score + per-function attribution. Never
        touches the bucket ladder — a unit whose merged CPG would raise
        :class:`OversizeGraphError` scores here per function."""
        faults.raise_if("serve.engine_raises")
        hier = self.hier
        with self._lock:
            before = hier.n_level1_dispatches + hier.n_fallback_dispatches
            out = hier.score_unit(functions, supergraph)
            self.n_dispatches += (hier.n_level1_dispatches
                                  + hier.n_fallback_dispatches - before)
        return out

    def submit(self, graphs, bucket: ServeBucket) -> PendingScore:
        """Latency-mode dispatch: pad, upload, launch — NO host sync. The
        device batch is donated to the warm compiled callable, so the
        launch consumes its input buffers and back-to-back submits pipeline
        on-device instead of round-tripping through the host per request.

        Thread-safe: the pad→upload→launch sequence runs under the engine
        lock, so concurrent callers cannot interleave donated buffers —
        each caller's :class:`PendingScore` owns exactly the device values
        its own dispatch produced."""
        if self._device_fn is None:
            raise RuntimeError(
                "submit() needs a live-model engine (device_fn) — artifact "
                "engines reduce host-side and only support score()")
        faults.raise_if("serve.engine_raises")
        import jax
        import jax.numpy as jnp

        graphs = list(graphs)
        with self._lock:
            batch = self._padded_batch(graphs, bucket, feat_only=True)
            dev = self._device_fn(jax.tree.map(jnp.asarray, batch))
            self.n_dispatches += 1
        self._record_dispatch("engine.submit", bucket, len(graphs))
        return PendingScore(dev, len(graphs))

    # -- warmup + warm store ------------------------------------------------

    def bucket_key(self, bucket: ServeBucket) -> str:
        """Warm-store content address of one bucket's compiled program."""
        import jax

        from .warmstore import bucket_artifact_key

        return bucket_artifact_key(
            self.vocab_hash, self.model_rev, self.precision,
            self.label_style, self.feat_keys, bucket.spec.max_graphs,
            bucket.spec.max_nodes, bucket.spec.max_edges,
            platform=jax.default_backend())

    def _dummy_graph(self) -> Graph:
        n = 2
        feats = {k: np.zeros(n, np.int32) for k in self.feat_keys}
        return Graph(senders=np.arange(n - 1, dtype=np.int32),
                     receivers=np.arange(1, n, dtype=np.int32),
                     node_feats=feats).with_self_loops()

    def _warm_cold(self, bucket: ServeBucket, g: Graph) -> None:
        """Compile the bucket's callable(s) the pre-store way. Calls the
        underlying fns directly, NOT :meth:`score`: the
        ``serve.engine_raises`` fault point poisons a *request's* batch —
        an armed ``@1`` spec must hit the first client, not kill the
        server during startup warmup."""
        if self._stacked_fn is not None:
            batches = [self._padded_batch([g] if i == 0 else [], bucket,
                                          feat_only=True)
                       for i in range(self.n_replicas)]
            import jax

            stacked = jax.tree.map(lambda *xs: np.stack(xs, axis=0), *batches)
            np.asarray(self._stacked_fn(stacked), np.float32)
            return
        batch = self._padded_batch([g], bucket)
        np.asarray(self._score_fn(batch), np.float32)
        if self._device_fn is not None:
            import jax
            import jax.numpy as jnp

            fbatch = batch._replace(node_feats={
                k: batch.node_feats[k] for k in self.feat_keys})
            with warnings.catch_warnings():
                # probs don't alias any int32 input leaf, so XLA reports
                # the donation as unusable at compile — expected here
                warnings.filterwarnings(
                    "ignore", message=".*donated.*", category=UserWarning)
                np.asarray(
                    self._device_fn(jax.tree.map(jnp.asarray, fbatch)))

    def _load_bucket_fn(self, payload: bytes):
        """Deserialize a warm-store payload into this bucket's score_fn
        (same feat-key conformance contract as the live path)."""
        import jax
        import jax.numpy as jnp

        from jax import export as jexport

        from deepdfa_tpu.serving import _register_pytrees

        _register_pytrees()
        exported = jexport.deserialize(payload)

        def fn(batch):
            batch = batch._replace(
                node_feats={k: batch.node_feats[k] for k in self.feat_keys})
            return np.asarray(exported.call(jax.tree.map(jnp.asarray, batch)),
                              np.float32)

        return fn

    def warmup(self, warm_store=None, journal=None) -> dict:
        """Warm every bucket's callable so the first real request never
        pays XLA compilation; returns a report dict (``buckets``, ``hits``,
        ``misses``, ``compile_seconds_saved``, ``per_bucket``).

        With a ``warm_store``, each bucket first tries the store: a HIT
        deserializes the content-addressed exported program (no trace, no
        lowering) and records ``compile_seconds_saved`` = the populating
        replica's recorded compile time minus this load's wall time; a
        MISS compiles cold and, when the engine can export (live
        single-replica, synchronous mode), commits the program for the
        next joiner. Journaled (``event="warmup"``) alongside the
        ``int8_gate_refused`` entries when ``journal`` is given."""
        use_store = (warm_store is not None and self._export_fn is not None
                     and not self.latency_mode)
        g = self._dummy_graph()
        report = {"buckets": len(self.buckets), "hits": 0, "misses": 0,
                  "compile_seconds_saved": 0.0, "per_bucket": {}}
        for b in self.buckets:
            key = self.bucket_key(b) if use_store else None
            entry = warm_store.get(key) if use_store else None
            row: dict = {"key": key}
            if entry is not None:
                t0 = time.perf_counter()
                fn = self._load_bucket_fn(entry.payload)
                fn(self._padded_batch([g], b))  # compiles the StableHLO once
                warm_s = time.perf_counter() - t0
                self._bucket_fns[b] = fn
                recorded = float(entry.meta.get("compile_seconds", 0.0))
                saved = max(0.0, recorded - warm_s)
                report["hits"] += 1
                report["compile_seconds_saved"] += saved
                row.update(source="store", warm_seconds=round(warm_s, 3),
                           compile_seconds=round(recorded, 3),
                           compile_seconds_saved=round(saved, 3))
            else:
                t0 = time.perf_counter()
                self._warm_cold(b, g)
                compile_s = time.perf_counter() - t0
                report["misses"] += 1
                row.update(source="compile",
                           compile_seconds=round(compile_s, 3))
                if use_store:
                    # a lowering/serialization failure is a bug and
                    # surfaces; only the store WRITE is best-effort (the
                    # store is an optimization — a full or read-only disk
                    # must not take down a warm, compiled bucket)
                    payload, export_s = self._export_fn(b)
                    row["export_seconds"] = round(export_s, 3)
                    try:
                        warm_store.put(key, payload, {
                            "compile_seconds": compile_s,
                            "vocab_hash": self.vocab_hash,
                            "model_rev": self.model_rev,
                            "precision": self.precision,
                            "label_style": self.label_style,
                            "graph_nodes": b.graph_nodes,
                            "spec": [b.spec.max_graphs, b.spec.max_nodes,
                                     b.spec.max_edges],
                        })
                    except OSError as exc:
                        warnings.warn(
                            f"warm-store write failed for bucket "
                            f"{b.graph_nodes}: {type(exc).__name__}: {exc}",
                            stacklevel=2)
                        row["export_error"] = f"{type(exc).__name__}: {exc}"
            report["per_bucket"][str(b.graph_nodes)] = row
        if self.mega_bucket is not None:
            # the packed-dispatch shape compiles like any ladder bucket;
            # it never exports (warm-store keys are ladder shapes) and is
            # reported under "mega" so ladder rows keep their node keys
            t0 = time.perf_counter()
            self._warm_cold(self.mega_bucket, g)
            report["per_bucket"]["mega"] = {
                "key": None, "source": "compile",
                "compile_seconds": round(time.perf_counter() - t0, 3)}
        report["compile_seconds_saved"] = round(
            report["compile_seconds_saved"], 3)
        self.warm_buckets = [b.graph_nodes for b in self.buckets]
        self.last_warmup_report = report
        if journal is not None:
            journal.write(event="warmup", vocab_hash=self.vocab_hash,
                          model_rev=self.model_rev, precision=self.precision,
                          **report)
        return report

    # -- constructors -------------------------------------------------------

    @classmethod
    def from_model(cls, model, params, label_style: str, feat_keys,
                   max_batch: int = 16, buckets=None,
                   vocab_hash: str | None = None, precision: str = "f32",
                   int8_max_score_delta: float = 0.01,
                   latency_mode: bool = False, calibration_graphs=None,
                   journal=None, mesh=None,
                   megabatch: bool = False) -> "ScoringEngine":
        """Live-model engine (the checkpoint path's core, split out so
        tests can inject fresh params without checkpoint machinery).

        ``precision="int8"`` quantizes the conv matmuls
        (:func:`~deepdfa_tpu.models.ggnn_int8.quantize_conv_params`) and
        GATES the result: f32 and int8 scores are compared on a
        calibration batch per bucket (``calibration_graphs`` or a
        synthesized set) and int8 is REFUSED — engine falls back to f32
        with a warning, journaled when ``journal`` (a ``RunJournal``) is
        given — if the max probability delta exceeds
        ``int8_max_score_delta``. ``latency_mode`` arms :meth:`submit`'s
        warm donated-buffer dispatch path.

        ``mesh`` (a ``jax.sharding.Mesh`` with a ``dp`` axis, e.g.
        :func:`deepdfa_tpu.parallel.mesh.local_mesh`) replicates the
        chosen scorer across every ``dp`` device: the engine scores
        ``dp``-stacked batches device-parallel via :meth:`score_groups`
        and the batcher packs across replicas. Mesh engines dispatch
        synchronously (no donated-buffer submit loop) and keep their
        compiled stack in-process (the warm store serves the
        single-replica router-fleet topology).

        ``megabatch=True`` additionally provisions the :func:`mega_bucket`
        cross-bucket packed-dispatch shape (warmed alongside the ladder)
        so :meth:`score_packed` can score a whole mixed-size request
        window in one dispatch instead of one per size class."""
        import functools

        import jax
        import jax.numpy as jnp

        from deepdfa_tpu.predict import make_scorer

        keys = tuple(feat_keys)
        buckets = tuple(buckets or serve_buckets(max_batch))
        mega = mega_bucket(max_batch) if megabatch else None
        model_rev = params_content_hash(params)

        def _fns(scorer, ps):
            def score_fn(batch):
                # conform to the warmed pytree structure: request graphs
                # carry extra columns the model never reads (``_VULN``
                # labels) — keep exactly ``feat_keys`` so every batch hits
                # ONE jit cache entry (same policy as serving._Servable)
                batch = batch._replace(
                    node_feats={k: batch.node_feats[k] for k in keys})
                fn_p, _ = scorer(ps, jax.tree.map(jnp.asarray, batch))
                return fn_p

            # the latency-mode entry: batch leaves are donated — the launch
            # consumes them, so a submitted buffer is dead to the host
            @functools.partial(jax.jit, donate_argnums=(0,))
            def device_fn(batch):
                fn_p, _ = scorer(ps, batch)
                return fn_p

            return score_fn, device_fn

        scorer_f32 = make_scorer(model, label_style)
        score_fn, device_fn = _fns(scorer_f32, params)
        chosen_model, chosen_params = model, params
        chosen_scorer = scorer_f32
        int8_delta = None
        if precision == "int8":
            accepted, int8_delta, reason = False, None, None
            try:
                from deepdfa_tpu.models.ggnn_int8 import (
                    GGNNInt8, quantize_conv_params)

                qparams = quantize_conv_params({"params": params})["params"]
                model8 = GGNNInt8(cfg=model.cfg, input_dim=model.input_dim)
                scorer8 = make_scorer(model8, label_style)
                score8, device8 = _fns(scorer8, qparams)
                cal = list(calibration_graphs or
                           _calibration_graphs(keys, buckets))
                int8_delta = 0.0
                for b in buckets:
                    gs = [g for g in cal if b.admits(g)][: b.capacity]
                    if not gs:
                        continue
                    batch = batch_np(gs, b.spec.max_graphs, b.spec.max_nodes,
                                     b.spec.max_edges)
                    p32 = np.asarray(score_fn(batch), np.float32)[: len(gs)]
                    p8 = np.asarray(score8(batch), np.float32)[: len(gs)]
                    int8_delta = max(int8_delta,
                                     float(np.max(np.abs(p32 - p8))))
                accepted = int8_delta <= int8_max_score_delta
                if not accepted:
                    reason = (f"max score delta {int8_delta:.2e} exceeds "
                              f"serve.int8_max_score_delta "
                              f"{int8_max_score_delta:.2e}")
            except ValueError as exc:  # e.g. NaN-poisoned checkpoint kernels
                reason = f"calibration refused: {exc}"
            if accepted:
                score_fn, device_fn = score8, device8
                chosen_model, chosen_params = model8, qparams
                chosen_scorer = scorer8
            else:
                warnings.warn(
                    f"int8 serving path refused — {reason}; serving f32",
                    stacklevel=2)
                if journal is not None:
                    journal.write(event="int8_gate_refused", reason=reason,
                                  int8_max_score_delta=int8_max_score_delta,
                                  int8_score_delta=int8_delta)
                precision = "f32"
        elif precision != "f32":
            raise ValueError(f"precision must be 'f32' or 'int8', got {precision!r}")

        # hierarchical whole-unit path: always the ORIGINAL f32 params —
        # the level-1 bit-identity invariant is pinned against the fused
        # f32 kernels, and the embedding cache keys on their model_rev
        hier_factory = None
        if getattr(model, "cfg", None) is not None:
            from deepdfa_tpu.models.ggnn_hier import (
                HierScorer, megabatch_compatible)

            if megabatch_compatible(model.cfg):
                hier_factory = (lambda m=model, p=params, rev=model_rev:
                                HierScorer(m.cfg, m.input_dim, p,
                                           model_rev=rev))

        if mesh is not None:
            stacked_fn = _make_replicated_fn(chosen_scorer, chosen_params,
                                             mesh)
            return cls(None, buckets, label_style=label_style,
                       feat_keys=keys, vocab_hash=vocab_hash,
                       latency_mode=latency_mode, precision=precision,
                       int8_score_delta=int8_delta, stacked_fn=stacked_fn,
                       n_replicas=int(mesh.shape["dp"]), model_rev=model_rev,
                       mega=mega, hier_factory=hier_factory)

        export_fn = _make_export_fn(chosen_model, chosen_params, label_style,
                                    keys)
        return cls(score_fn, buckets, label_style=label_style,
                   feat_keys=keys, vocab_hash=vocab_hash,
                   device_fn=device_fn, latency_mode=latency_mode,
                   precision=precision, int8_score_delta=int8_delta,
                   model_rev=model_rev, export_fn=export_fn, mega=mega,
                   hier_factory=hier_factory)

    @classmethod
    def from_checkpoint(cls, cfg, ckpt_dir: Path | str, vocabs,
                        max_batch: int | None = None,
                        journal=None) -> "ScoringEngine":
        """Restore best-else-latest params (same policy as predict/test)
        and serve through the layout-portable segment forward. With
        ``cfg.serve.mesh_replicas > 1`` the engine replicates across that
        many local devices (one replica per device)."""
        import jax
        import jax.numpy as jnp

        from deepdfa_tpu.models import make_model
        from deepdfa_tpu.pipeline import vocab_content_hash
        from deepdfa_tpu.train.checkpoint import CheckpointManager

        if cfg.model.layout != "segment":
            cfg = dataclasses.replace(
                cfg, model=dataclasses.replace(cfg.model, layout="segment"))
        model = make_model(cfg.model, cfg.input_dim)
        n = 4
        feats = {k: np.zeros(n, np.int32) for k in vocabs}
        feats["_VULN"] = np.zeros(n, np.int32)
        dummy = Graph(senders=np.arange(n - 1, dtype=np.int32),
                      receivers=np.arange(1, n, dtype=np.int32),
                      node_feats=feats).with_self_loops()
        example = jax.tree.map(jnp.asarray, batch_np([dummy], 2, 8, 128))
        params = model.init(jax.random.key(0), example)["params"]
        ckpts = CheckpointManager(Path(ckpt_dir), cfg.checkpoint)
        if ckpts.latest_step() is None:
            raise FileNotFoundError(
                f"no checkpoint under {ckpt_dir} — the engine serves a "
                "TRAINED model; run fit first (or point at an --artifact)")
        restored = (ckpts.restore_best(template={"params": params})
                    if ckpts.best_step() is not None
                    else ckpts.restore_latest(template={"params": params}))
        mesh = None
        if getattr(cfg.serve, "mesh_replicas", 0) > 1:
            from deepdfa_tpu.parallel.mesh import local_mesh

            mesh = local_mesh(cfg.serve.mesh_replicas)
        return cls.from_model(
            model, restored["params"], cfg.model.label_style,
            feat_keys=tuple(vocabs),
            max_batch=max_batch or cfg.serve.max_batch,
            vocab_hash=vocab_content_hash(vocabs),
            precision=cfg.serve.precision,
            int8_max_score_delta=cfg.serve.int8_max_score_delta,
            latency_mode=cfg.serve.latency_mode, journal=journal, mesh=mesh)

    @classmethod
    def from_artifact(cls, artifact_dir: Path | str,
                      vocabs=None) -> "ScoringEngine":
        """Engine over a pre-exported StableHLO artifact. The artifact is
        compiled for ONE shape, so the ladder collapses to one bucket at
        the manifest's budgets. When ``vocabs`` is given, its content hash
        is checked against the manifest (``load_exported`` warns on
        mismatch — the stale-artifact guard)."""
        from deepdfa_tpu.serving import load_exported

        vocab_hash = None
        if vocabs is not None:
            from deepdfa_tpu.pipeline import vocab_content_hash

            vocab_hash = vocab_content_hash(vocabs)
        servable = load_exported(artifact_dir, expect_vocab_hash=vocab_hash)
        man = servable.manifest
        leaves = man["input_leaves"]
        # flatten order: node_feats (sorted keys), senders, receivers,
        # node_gidx, node_mask, edge_mask, graph_mask
        max_graphs = int(leaves[-1]["shape"][0])
        max_edges = int(leaves[-2]["shape"][0])
        max_nodes = int(leaves[-3]["shape"][0])
        spec = BucketSpec(max_graphs, max_nodes, max_edges)
        bucket = ServeBucket(spec=spec, graph_nodes=max_nodes - 1)
        label_style = man.get("label_style", "graph")

        if label_style == "node":
            def score_fn(batch):
                node_p = np.asarray(servable(batch), np.float32)
                fn = np.zeros(batch.max_graphs, np.float32)
                mask = np.asarray(batch.node_mask)
                np.maximum.at(
                    fn, np.asarray(batch.node_gidx)[mask], node_p[mask])
                return fn
        else:
            score_fn = servable
        return cls(score_fn, (bucket,), label_style=label_style,
                   feat_keys=tuple(man["node_feat_keys"]),
                   vocab_hash=man.get("vocab_hash"))


# ---------------------------------------------------------------------------
# mesh replication + warm-store export helpers (live-model engines)


def _plain_score_callable(model, params, label_style: str):
    """The exportable form of the scorer: plain apply (no mutable
    intermediates — jax.export cannot serialize them), same probabilities
    as :func:`deepdfa_tpu.predict.make_scorer`. Node-style checkpoints
    bake the node→function max reduction into the program."""
    import jax
    import jax.numpy as jnp

    def score(batch):
        if label_style == "node":
            node_p = jax.nn.sigmoid(model.apply({"params": params}, batch))
            masked = jnp.where(batch.node_mask, node_p,
                               jnp.full_like(node_p, -jnp.inf))
            return jax.ops.segment_max(masked, batch.node_gidx,
                                       num_segments=batch.max_graphs)
        return jax.nn.sigmoid(model.apply({"params": params}, batch))

    return score


def _make_export_fn(model, params, label_style: str, feat_keys):
    """``bucket -> (serialized StableHLO, export_seconds)`` for the warm
    store — the same ``jax.export`` path :func:`deepdfa_tpu.serving.
    export_ggnn` uses, specialized to one bucket's padded shape and lowered
    for THIS host's platform only: the trace already chose Mosaic or the
    Pallas interpreter from the backend (the int8 conv), and a Mosaic
    kernel has no CPU lowering (``ValueError: Only interpret mode is
    supported on CPU backend``) while an interpret-mode trace would hand a
    TPU joiner the interpreter. The store key carries the platform."""

    def export_bucket(bucket: ServeBucket):
        import jax

        from jax import export as jexport

        from deepdfa_tpu.serving import _register_pytrees

        _register_pytrees()
        t0 = time.perf_counter()
        n = 2
        feats = {k: np.zeros(n, np.int32) for k in feat_keys}
        g = Graph(senders=np.arange(n - 1, dtype=np.int32),
                  receivers=np.arange(1, n, dtype=np.int32),
                  node_feats=feats).with_self_loops()
        ex = batch_np([g], bucket.spec.max_graphs, bucket.spec.max_nodes,
                      bucket.spec.max_edges)
        ex = ex._replace(node_feats={k: ex.node_feats[k] for k in feat_keys})
        args_spec = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype),
            ex)
        score = _plain_score_callable(model, params, label_style)
        exported = jexport.export(
            jax.jit(score), platforms=[jax.default_backend()])(args_spec)
        return exported.serialize(), time.perf_counter() - t0

    return export_bucket


def _make_replicated_fn(scorer, params, mesh):
    """One-dispatch device-parallel scoring over a ``dp`` mesh: the
    stacked ``[dp, ...]`` batch splits one padded batch per device
    (shard_map), each replica runs the scorer locally, and the probs come
    back stacked ``[dp, max_graphs]``. Params are replicated — no
    collectives exist in this program at all; it is pure replication."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    def one(ps, stacked):
        batch = jax.tree.map(lambda x: x[0], stacked)
        fn_p, _ = scorer(ps, batch)
        return fn_p[None]

    replicated = jax.jit(jax.shard_map(
        one, mesh=mesh, in_specs=(P(), P("dp")), out_specs=P("dp"),
        check_vma=False))

    def stacked_fn(stacked):
        return np.asarray(
            replicated(params, jax.tree.map(jnp.asarray, stacked)),
            np.float32)

    return stacked_fn
