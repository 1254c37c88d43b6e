"""Training/eval CLI — the ``main_cli.py`` replacement.

Subcommands (parity with ``DDFA/code_gnn/main_cli.py`` +
``DDFA/scripts/{train,test,run_analyze_dataset}.sh``):

- ``fit``     — train with per-epoch undersample re-draws, per-epoch val,
  best/last/periodic checkpoints, then restore the best checkpoint and
  re-validate (``main_cli.py:167-184``).
- ``test``    — restore a checkpoint and evaluate: overall + positive-only +
  negative-only metric collections, PR curves → ``pr.csv``/``pr_binned.csv``,
  classification report + confusion matrix, optional FLOPs/latency profiling
  (``base_module.py:238-323,348-383``).
- ``analyze`` — dataset coverage statistics (``--analyze_dataset``,
  ``main_cli.py:192-313``): feature coverage per split, label balance.

Config: layered YAML/JSON via ``--config a.yaml --config b.yaml`` (later
wins) + dotted ``--set key.sub=value`` overrides — the LightningCLI layering
semantics with typed validation (``deepdfa_tpu/config.py``).

Logging: stream + per-run logfile; the logfile is renamed ``*.log.error`` on
crash (``main_cli.py:322-336``). Per-epoch val F1 and the final F1 are
appended to ``tuning.jsonl`` — the NNI intermediate/final reporting analogue
(``base_module.py:346``, ``main_cli.py:184``).

Data: loads materialised shards + ``splits.json`` from
``processed_dir()/{dsname}/shards[_sample]`` when present, else falls back to
a deterministic synthetic corpus (hermetic smoke/bench mode — the real
Big-Vul corpus needs the offline extraction pipeline).
"""

from __future__ import annotations

import argparse
import json
import logging
import sys
import time
from pathlib import Path
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deepdfa_tpu import utils
from deepdfa_tpu.config import ExperimentConfig, load_config
from deepdfa_tpu.data.graphs import BucketSpec, Graph, GraphBatcher, load_shards
from deepdfa_tpu.data.sampler import epoch_indices, positive_weight
from deepdfa_tpu.models import make_model
from deepdfa_tpu.train import metrics as M
from deepdfa_tpu.resilience.journal import atomic_write_text
from deepdfa_tpu.train.checkpoint import CheckpointManager
from deepdfa_tpu.train.loop import Trainer, _weighted_mean

logger = logging.getLogger("deepdfa_tpu")

__all__ = ["main", "fit", "test", "analyze", "load_corpus", "coverage"]


# ---------------------------------------------------------------------------
# data loading


def _synthetic_corpus(cfg: ExperimentConfig) -> dict[str, list[Graph]]:
    from deepdfa_tpu.data.synthetic import random_dataset

    n = 600 if not cfg.data.sample else 200
    graphs = random_dataset(n, seed=cfg.data.seed, input_dim=cfg.input_dim)
    rng = np.random.default_rng(cfg.data.seed)
    assign = rng.permutation(n)
    n_val, n_test = int(n * 0.1), int(n * 0.2)
    val_ids = set(assign[:n_val].tolist())
    test_ids = set(assign[n_val:n_test].tolist())
    out: dict[str, list[Graph]] = {"train": [], "val": [], "test": []}
    for g in graphs:
        part = "val" if g.gid in val_ids else "test" if g.gid in test_ids else "train"
        out[part].append(g)
    return out


def load_corpus(cfg: ExperimentConfig) -> dict[str, list[Graph]]:
    """{split: [Graph]} from materialised shards, or synthetic fallback."""
    sample_text = "_sample" if cfg.data.sample else ""
    shard_dir = utils.processed_dir() / cfg.data.dsname / f"shards{sample_text}"
    splits_file = shard_dir / "splits.json"
    if shard_dir.exists() and splits_file.exists():
        graphs = load_shards(shard_dir)
        if cfg.data.split not in ("fixed", "random"):
            # load-time re-partition by a NAMED split (the reference's
            # `--data.split cross_project_fold_N_{dataset,holdout}`,
            # run_cross_project.sh): the shards and their vocabulary stay
            # as preprocessed — only the partition changes, exactly like
            # test.sh re-splitting at load
            from deepdfa_tpu.data import ingest

            smap = ingest.named_splits(cfg.data.split).to_dict()
            by_gid = {g.gid: g for g in graphs}
            id_splits, missing = ingest.partition_ids(sorted(by_gid), smap)
            if sum(len(v) for v in id_splits.values()) == 0:
                raise ValueError(
                    f"named split {cfg.data.split!r} matched NONE of the "
                    f"{len(by_gid)} shard graph ids — wrong split file for "
                    "this corpus?")
            if missing:
                logger.warning(
                    "%d graphs not in named split %r dropped",
                    missing, cfg.data.split)
            return {part: [by_gid[i] for i in ids_]
                    for part, ids_ in id_splits.items()}
        splits = {k: set(v) for k, v in json.loads(splits_file.read_text()).items()}
        # split-leakage guard (reference linevd/datamodule.py:75-78: train/val/
        # test id sets must be pairwise disjoint at construction)
        for a in ("train", "val", "test"):
            for b in ("train", "val", "test"):
                if a < b and splits.get(a, set()) & splits.get(b, set()):
                    overlap = sorted(splits[a] & splits[b])[:5]
                    raise ValueError(
                        f"split leakage: {a}∩{b} non-empty (e.g. {overlap}) "
                        f"in {splits_file}"
                    )
        out: dict[str, list[Graph]] = {"train": [], "val": [], "test": []}
        missing = 0
        for g in graphs:
            for part in out:
                if g.gid in splits.get(part, ()):
                    out[part].append(g)
                    break
            else:
                missing += 1
        if missing:
            logger.warning("%d graphs without split assignment dropped", missing)
        return out
    logger.warning(
        "no materialised shards at %s — using the synthetic corpus", shard_dir
    )
    return _synthetic_corpus(cfg)


def _batcher(cfg: ExperimentConfig, graphs: list[Graph] | None = None):
    """Fixed-shape batcher for the configured graph layout. With
    ``auto_buckets`` and a corpus to measure, budgets come from corpus
    statistics (capped by the configured ceilings) instead of the worst-case
    constants — padding is wasted FLOPs on TPU."""
    b = cfg.data.batch
    if cfg.model.layout == "dense":
        from deepdfa_tpu.data.dense import DenseBatcher, derive_dense_sizes

        # per-graph ceiling from the configured TOTAL node budget: a batch
        # never holds more than max_nodes slots, so adjacency memory stays
        # bounded on heavy-tailed corpora (bigger graphs route through the
        # segment-fallback overflow below)
        cap = max(b.max_nodes // max(b.batch_graphs, 1), 8)
        if b.auto_buckets and graphs:
            # corpus-size-aware shape count: the DP's occupancy win assumes
            # batches actually FILL; the trainer's streaming mode flushes one
            # partial batch per shape per pass, so cap k near the expected
            # number of full batches (small demo corpora keep the old 2-shape
            # behavior; big corpora get the full k=6 split)
            k = int(np.clip(round(len(graphs) / max(b.batch_graphs, 1)), 1, 6))
            sizes = sorted({min(s, cap) for s in derive_dense_sizes(graphs, k=k)})
        else:
            sizes = [cap]
        # drop_oversize=True means "don't error on oversize" — but a trainer
        # must never silently truncate its corpus, so oversize graphs are
        # COLLECTED and routed through the segment-layout fallback forward
        # (same params) by _batch_stream; drop_oversize=False keeps its
        # strict raise semantics.
        return _with_overflow_bucket(
            DenseBatcher(
                max_graphs=b.batch_graphs,
                nodes_per_graph=sizes,
                drop_oversize=False,
                collect_oversize=b.drop_oversize,
            ),
            graphs,
        )
    # segment AND fused layouts batch identically (fused consumes segment
    # BatchedGraphs; the Trainer drops VMEM-oversized buckets to its segment
    # twin per batch, so no batcher-side special-casing is needed)
    if b.auto_buckets and graphs:
        from deepdfa_tpu.data.graphs import derive_buckets

        buckets = [
            BucketSpec(
                max_graphs=min(s.max_graphs, b.batch_graphs + 1),
                max_nodes=min(s.max_nodes, b.max_nodes),
                max_edges=min(s.max_edges, b.max_edges),
            )
            for s in derive_buckets(graphs, b.batch_graphs)
        ]
        batcher = GraphBatcher(buckets, drop_oversize=False,
                               collect_oversize=b.drop_oversize)
    else:
        batcher = GraphBatcher(
            [BucketSpec(b.batch_graphs + 1, b.max_nodes, b.max_edges)],
            drop_oversize=False,
            collect_oversize=b.drop_oversize,
        )
    return _with_overflow_bucket(batcher, graphs)


def _overflow_bucket_for(graphs: Sequence[Graph]) -> BucketSpec:
    """One rescue graph per overflow batch, sized ~1x the largest oversize
    graph (r04 advisor: the previous 4x-nodes-AND-edges x 4-graph budget
    padded every overflow batch to 16x the global max on heavy-tailed
    corpora — host/device OOM risk for zero benefit)."""
    from deepdfa_tpu.data.graphs import _round_up

    mn = _round_up(max(g.n_nodes for g in graphs) + 2)
    me = max(_round_up(max(g.n_edges for g in graphs)), 128)
    return BucketSpec(max_graphs=2, max_nodes=mn, max_edges=me)


def _with_overflow_bucket(batcher, graphs):
    """Pre-size the oversize rescue bucket from the FULL corpus so its
    compiled shape is fixed across epochs/splits (per-pass re-derivation
    would churn XLA compiles as undersampling includes/excludes the largest
    graphs)."""
    if graphs:
        if hasattr(batcher, "big"):  # segment layout
            over = [g for g in graphs
                    if not batcher.big.fits(1, g.n_nodes, g.n_edges)]
        else:  # dense layout: per-graph node budget
            over = [g for g in graphs if g.n_nodes > batcher.nodes_per_graph]
        if over:
            batcher.overflow_bucket = _overflow_bucket_for(over)
    return batcher


def _oversize_upfront(batcher, graphs: list[Graph]) -> list[Graph]:
    """The graphs the primary batcher would route to its oversize list —
    same fits logic as ``_with_overflow_bucket``, computable before any
    batch is built."""
    if hasattr(batcher, "big"):  # segment layout
        return [g for g in graphs
                if not batcher.big.fits(1, g.n_nodes, g.n_edges)]
    return [g for g in graphs if g.n_nodes > batcher.nodes_per_graph]


def _overflow_batches(batcher, leftover: list[Graph]):
    if not leftover:
        return
    bucket = getattr(batcher, "overflow_bucket", None)
    if bucket is None or not all(
        bucket.fits(1, g.n_nodes, g.n_edges) for g in leftover
    ):
        bucket = _overflow_bucket_for(leftover)
    seg = GraphBatcher([bucket], drop_oversize=False)
    yield from seg.batches(leftover)


def _batch_stream(batcher, graphs: list[Graph], shuffle_seed: int | None = None):
    """All batches for one pass: the primary layout's batches plus the
    oversize overflow as segment-layout batches through a dedicated big
    bucket, so every graph is scored (for the dense layout the Trainer
    routes overflow through the segment twin of the same params; for the
    segment layout it is simply one more compiled shape).

    Eval passes stream primary-then-overflow (order is irrelevant there).
    TRAINING passes pass ``shuffle_seed``: overflow batches are interleaved
    at seeded-random positions instead of trailing every epoch — the r04
    advisor flagged the tail placement as a systematic ordering bias (the
    largest graphs always trained last, outside the shuffled stream). The
    primary stream stays a GENERATOR (an epoch's padded batches held
    resident would be multi-GB on a large corpus): the oversize set is
    computed up-front with the batcher's own fits logic, its (few, one-
    graph) batches are built eagerly, and each is emitted when the primary
    stream's real-graph progress crosses a seeded uniform threshold —
    uniform-in-expectation placement with O(#oversize) extra memory."""
    if shuffle_seed is None:
        yield from batcher.batches(graphs)
        yield from _overflow_batches(
            batcher, list(getattr(batcher, "oversize_graphs", None) or ())
        )
        return

    over = _oversize_upfront(batcher, graphs)
    if not over:
        yield from batcher.batches(graphs)
        return
    over_gids = {g.gid for g in over}
    keep = [g for g in graphs if g.gid not in over_gids]
    overflow = list(_overflow_batches(batcher, over))
    rng = np.random.default_rng(shuffle_seed)
    thresholds = np.sort(rng.random(len(overflow)))
    oi = 0
    consumed = 0
    for b in batcher.batches(keep):
        frac = consumed / max(len(keep), 1)
        while oi < len(overflow) and thresholds[oi] <= frac:
            yield overflow[oi]
            oi += 1
        yield b
        consumed += int(np.asarray(b.graph_mask).sum())
    while oi < len(overflow):
        yield overflow[oi]
        oi += 1
    # keep the routing counters honest for _oversize_stats: the primary
    # batcher never saw the oversize graphs on this path
    batcher.oversize_graphs = list(over)


def _oversize_stats(batcher, suffix: str = "") -> dict[str, int]:
    """Routing counters for the last-consumed pass (ADVICE r03: surfaced in
    metrics JSON, not just attributes): n_dropped must stay 0 in trainer
    configurations. ``suffix`` names the pass (e.g. ``_train``/``_val``)
    because the counters reset every ``batches()`` call."""
    return {
        f"n_dropped{suffix}": int(getattr(batcher, "n_dropped", 0)),
        f"n_oversize_fallback{suffix}":
            len(getattr(batcher, "oversize_graphs", ()) or ()),
    }


def _epoch_graphs(
    train: list[Graph], labels: np.ndarray, cfg: ExperimentConfig, epoch: int
) -> list[Graph]:
    idx = epoch_indices(
        labels,
        undersample=cfg.data.undersample,
        oversample=cfg.data.oversample,
        seed=cfg.data.seed,
        epoch=epoch,
    )
    return [train[i] for i in idx]


# ---------------------------------------------------------------------------
# subcommands


def _tb_writer(run_dir: Path):
    """TensorBoard scalars (``MyTensorBoardLogger`` parity, ``my_tb.py:5-8``);
    optional — the jsonl/json artifacts are the primary record."""
    try:
        from torch.utils.tensorboard import SummaryWriter
    except ImportError:
        return None
    return SummaryWriter(log_dir=str(run_dir / "tb"))


def fit(cfg: ExperimentConfig, run_dir: Path, resume: bool = False) -> dict[str, float]:
    from deepdfa_tpu.parallel.elastic import mesh_block
    from deepdfa_tpu.resilience import (
        DivergenceError,
        DivergenceSentinel,
        HangWatchdog,
        Preempted,
        PreemptedExit,
        PreemptionHandler,
        RunJournal,
        WatchdogTimeout,
    )
    from deepdfa_tpu.train.loop import TrainState

    corpus = load_corpus(cfg)
    train, val = corpus["train"], corpus["val"]
    train_labels = np.array([int(g.node_feats["_VULN"].max()) for g in train])
    pos_weight = positive_weight(train_labels)
    logger.info(
        "corpus: train=%d val=%d test=%d pos_weight=%.2f",
        len(train), len(val), len(corpus["test"]), pos_weight,
    )

    model = make_model(cfg.model, cfg.input_dim)
    trainer = Trainer(model, cfg, pos_weight=pos_weight)
    batcher = _batcher(cfg, train + val)
    example = jax.tree.map(
        jnp.asarray,
        next(_batch_stream(batcher, train[: cfg.data.batch.batch_graphs])),
    )
    state = trainer.init_state(example)
    ckpts = CheckpointManager(run_dir / "checkpoints", cfg.checkpoint)
    journal = RunJournal(run_dir / "journal.json")
    res = cfg.resilience
    sentinel = (
        DivergenceSentinel(patience=res.sentinel_patience, lag=res.sentinel_lag)
        if res.sentinel
        else None
    )
    tuning_file = run_dir / "tuning.jsonl"
    tb = _tb_writer(run_dir)
    topology = mesh_block()  # recorded in every meta.json for elastic resume
    preemption = PreemptionHandler().install() if res.emergency_ckpt else None
    watchdog = (
        HangWatchdog(res.step_deadline_s) if res.step_deadline_s > 0 else None
    )
    # training telemetry (obs.TrainTelemetry): per-step timelines into the
    # per-epoch journal, step spans into <run>/traces/ (exported by
    # `deepdfa-tpu trace export`), and an optional scrape endpoint
    obs = cfg.serve.obs
    telemetry = None
    telemetry_server = None
    if obs.trace:
        from deepdfa_tpu.obs import (
            FlightRecorder,
            SLOEngine,
            TelemetryServer,
            Tracer,
            TrainTelemetry,
            train_specs,
        )
        from deepdfa_tpu.obs.flightrec import install_sigusr2

        flight = FlightRecorder(
            capacity=obs.flight_events, proc="train",
            dump_dir=Path(obs.flight_dir) if obs.flight_dir else run_dir)
        slo = SLOEngine(
            train_specs(step_ms=obs.slo_step_ms),
            fast_window_s=obs.slo_fast_window_s,
            slow_window_s=obs.slo_slow_window_s,
            burn_threshold=obs.slo_burn_threshold,
            flight=flight)
        telemetry = TrainTelemetry(tracer=Tracer(
            proc="train", max_spans=obs.trace_buffer,
            slow_ms=0.0,  # journal every epoch root, capped by max_exemplars
            exemplar_dir=(Path(obs.trace_dir) if obs.trace_dir
                          else run_dir / "traces"),
            max_exemplars=obs.max_exemplars,
            annotation=jax.profiler.TraceAnnotation),
            slo=slo, flight=flight)
        install_sigusr2(flight)  # no-op off the main thread
        if obs.train_port >= 0:
            telemetry_server = TelemetryServer(
                telemetry, port=obs.train_port).start()
            logger.info("trainer telemetry on :%d (/metrics, /healthz, /slo)",
                        telemetry_server.port)

    def _aux(s: TrainState) -> dict:
        # the trainer state beyond params — what bit-identical resume needs
        # (typed PRNG keys serialise via key_data / wrap_key_data)
        return {
            "opt_state": s.opt_state,
            "rng": jax.random.key_data(s.rng),
            "step": s.step,
        }

    aux_template = _aux(state)

    def _restore_full(reason: str) -> tuple[TrainState, dict]:
        """(restored TrainState, checkpoint meta); walks past corrupt
        steps (restore_resume), so a damaged newest checkpoint falls back
        to the previous good one. A checkpoint recorded under a different
        mesh/topology (elastic resume: dp=N run coming back on a smaller
        harness) is rehydrated host-side and re-placed — values are
        bit-identical, only the placement changes."""
        from deepdfa_tpu.parallel.elastic import elastic_restore

        step, meta, payload, aux, resharded = elastic_restore(
            ckpts, template={"params": state.params}, aux_template=aux_template
        )
        if resharded:
            logger.warning(
                "%s: mesh changed since checkpoint (%s -> %s) — "
                "host-gathered and re-placed params/opt-state", reason,
                meta.get("mesh"), topology,
            )
        restored = TrainState(
            payload["params"],
            aux["opt_state"],
            jax.random.wrap_key_data(aux["rng"]),
            aux["step"],
        )
        logger.info("%s: restored checkpoint step=%d (epoch %s)",
                    reason, step, meta.get("epoch"))
        meta = dict(meta)
        meta["_resharded"] = resharded
        return restored, meta

    start_epoch = 0
    n_rollbacks = 0
    pre_skip = 0  # mid-epoch resume: batches of start_epoch already consumed
    resharded = False
    if resume:
        rec = journal.read()
        if rec is None or ckpts.latest_step() is None:
            logger.warning(
                "--resume: no journal/checkpoint under %s — starting fresh", run_dir
            )
        else:
            # the checkpoint's recorded epoch (its commit is atomic) decides
            # where training restarts; the journal carries the advisory
            # run-level extras (rollback count, LR escalation)
            state, meta = _restore_full("resume")
            ckpt_epoch = int(meta.get("epoch", -1))
            resharded = bool(meta.get("_resharded"))
            pre = meta.get("preempted")
            if pre:
                # emergency checkpoint: re-enter the SAME epoch and skip the
                # batches it already executed — the deterministic epoch
                # stream + restored rng make the continuation bit-identical
                start_epoch = ckpt_epoch
                pre_skip = int(pre.get("steps_done", 0))
                logger.info(
                    "resume after preemption (%s): re-entering epoch %d at "
                    "step offset %d", pre.get("reason"), start_epoch, pre_skip,
                )
            else:
                start_epoch = ckpt_epoch + 1
            n_rollbacks = int(rec.get("rollbacks", 0))
            lr_scale = float(rec.get("lr_scale", 1.0))
            if lr_scale != trainer.lr_scale:
                trainer.rescale_lr(lr_scale / trainer.lr_scale)
            logger.info(
                "resume: epoch %d..%d (rollbacks=%d lr_scale=%.3g)",
                start_epoch, cfg.optim.max_epochs - 1, n_rollbacks, trainer.lr_scale,
            )

    last_val: dict[str, float] = {}
    route: dict[str, int] = {}
    # per-epoch step accounting of THIS process (steps, compiles, twin-routed
    # steps): the journal is single-record, so the completed record carries
    # the list — "epoch 2 compiled nothing" stays checkable after the run
    epoch_rows: list[dict] = []
    epoch = start_epoch
    try:
        while epoch < cfg.optim.max_epochs:
            epoch_gs = _epoch_graphs(train, train_labels, cfg, epoch)
            # mid-epoch resume: skip the batches the preempted run already
            # executed — only on the first (re-entered) epoch; a rollback
            # retry of that epoch restores the same emergency checkpoint,
            # so the offset stays valid
            skip = pre_skip if epoch == start_epoch else 0
            if telemetry is not None:
                telemetry.observe_epoch(epoch)
            try:
                state, train_m, train_loss = trainer.train_epoch(
                    state,
                    _batch_stream(batcher, epoch_gs, shuffle_seed=cfg.seed + epoch),
                    sentinel=sentinel,
                    preemption=preemption,
                    skip_steps=skip,
                    watchdog=watchdog,
                    telemetry=telemetry,
                )
            except Preempted as p:
                # deadline-bounded emergency checkpoint through the ordinary
                # atomic commit protocol, then exit with the resumable rc
                state = p.state
                elapsed = ckpts.save_emergency(
                    int(state.step), {"params": state.params},
                    epoch=epoch, aux=_aux(state), mesh=topology,
                    steps_done=p.steps_done, reason=p.reason,
                )
                within = elapsed <= res.preempt_deadline_s
                logger.log(
                    logging.INFO if within else logging.ERROR,
                    "emergency checkpoint step=%d committed in %.2fs "
                    "(deadline %.0fs%s) — epoch %d, %d step(s) done, rc=%d",
                    int(state.step), elapsed, res.preempt_deadline_s,
                    "" if within else " EXCEEDED", epoch, p.steps_done,
                    PreemptedExit().code,
                )
                journal.write(
                    epoch=epoch,
                    global_step=int(state.step),
                    seed=cfg.seed,
                    preempted=p.reason,
                    preempted_steps_done=p.steps_done,
                    emergency_commit_s=round(elapsed, 3),
                    emergency_deadline_s=res.preempt_deadline_s,
                    mesh=topology,
                    lr_scale=trainer.lr_scale,
                    rollbacks=n_rollbacks,
                )
                raise PreemptedExit(p.reason)
            except WatchdogTimeout as wt:
                # a wedged device call: journal the timeout and abort —
                # bounded and diagnosable instead of an eternal hang. The
                # flight recorder dumps its ring first: the last-N events
                # (steps, faults, ckpt commits) around the wedge are the
                # post-mortem an aborted process can't reconstruct.
                if telemetry is not None:
                    telemetry.record_event(
                        "watchdog.timeout", point=wt.point,
                        deadline_s=wt.deadline_s, epoch=epoch,
                        step=int(state.step))
                    if telemetry.flight is not None:
                        telemetry.flight.dump("watchdog_timeout")
                journal.write(
                    epoch=epoch,
                    global_step=int(state.step),
                    seed=cfg.seed,
                    watchdog_timeout={"point": wt.point,
                                      "deadline_s": wt.deadline_s},
                    lr_scale=trainer.lr_scale,
                    rollbacks=n_rollbacks,
                )
                logger.error("%s — aborting (journaled)", wt)
                raise
            except DivergenceError as err:
                n_rollbacks += 1
                sentinel.reset()
                if n_rollbacks > res.max_rollbacks:
                    logger.error(
                        "divergence persisted past %d rollbacks — aborting",
                        res.max_rollbacks,
                    )
                    raise
                trainer.rescale_lr(res.lr_backoff)
                if ckpts.latest_step() is not None:
                    state, _meta = _restore_full(f"rollback ({err})")
                else:
                    logger.warning("diverged before the first checkpoint — re-initialising")
                    state = trainer.init_state(example)
                logger.warning(
                    "rollback %d/%d: lr_scale=%.3g, retrying epoch %d",
                    n_rollbacks, res.max_rollbacks, trainer.lr_scale, epoch,
                )
                if telemetry is not None:
                    telemetry.record_event(
                        "sentinel.rollback", rollback=n_rollbacks,
                        epoch=epoch, lr_scale=trainer.lr_scale)
                continue
            route = _oversize_stats(batcher, "_train")
            val_m, val_loss = trainer.evaluate(state.params, _batch_stream(batcher, val))
            route |= _oversize_stats(batcher, "_val")
            last_val = val_m
            logger.info(
                "epoch %d: train_loss=%.4f train_F1=%.4f val_loss=%.4f val_F1=%.4f"
                " oversize_fallback=%d/%d dropped=%d/%d (train/val)",
                epoch, train_loss, train_m["train_F1Score"], val_loss, val_m["val_F1Score"],
                route["n_oversize_fallback_train"], route["n_oversize_fallback_val"],
                route["n_dropped_train"], route["n_dropped_val"],
            )
            if tb is not None:
                for k, v in {"train_loss": train_loss, "val_loss": val_loss,
                             **train_m, **val_m}.items():
                    tb.add_scalar(k, v, epoch)
            t_ckpt = time.time()
            ckpts.save(
                int(state.step), {"params": state.params},
                metrics={"val_loss": val_loss, "val_F1Score": val_m["val_F1Score"]},
                epoch=epoch,
                aux=_aux(state),
                mesh=topology,
            )
            if telemetry is not None:
                telemetry.tracer.record("ckpt.commit", t_ckpt,
                                        step=int(state.step), epoch=epoch)
                telemetry.record_event("ckpt.commit", step=int(state.step),
                                       epoch=epoch)
            epoch_rows.append({
                "epoch": epoch,
                "twin_routed_steps": trainer.twin_routed_steps,
                **({"telemetry": telemetry.epoch_stats()}
                   if telemetry is not None else {}),
            })
            journal.write(
                global_step=int(state.step),
                seed=cfg.seed,
                sampler={
                    "seed": cfg.data.seed,
                    "undersample": cfg.data.undersample,
                    "oversample": cfg.data.oversample,
                    "epoch": epoch,
                },
                best_metric=ckpts.best_metric(),
                lr_scale=trainer.lr_scale,
                rollbacks=n_rollbacks,
                mesh=topology,
                resharded=resharded,
                **(sentinel.stats() if sentinel is not None else {}),
                **epoch_rows[-1],
            )
            with open(tuning_file, "a") as f:
                f.write(json.dumps({"epoch": epoch, "val_F1Score": val_m["val_F1Score"]}) + "\n")
            if preemption is not None and preemption.triggered:
                # the notice landed during val/checkpointing: this epoch's
                # NORMAL checkpoint is already committed — exit resumable
                # without an extra emergency save
                journal.write(
                    epoch=epoch,
                    global_step=int(state.step),
                    seed=cfg.seed,
                    preempted=preemption.reason,
                    preempted_steps_done=0,
                    emergency_commit_s=0.0,
                    emergency_deadline_s=res.preempt_deadline_s,
                    mesh=topology,
                    lr_scale=trainer.lr_scale,
                    rollbacks=n_rollbacks,
                )
                logger.info(
                    "preemption (%s) at epoch boundary — epoch %d checkpoint "
                    "already committed", preemption.reason, epoch,
                )
                raise PreemptedExit(preemption.reason)
            epoch += 1
    finally:
        if preemption is not None:
            preemption.uninstall()
        if telemetry_server is not None:
            telemetry_server.stop()

    # post-fit: restore best checkpoint and re-validate (main_cli.py:175-184)
    best_step = ckpts.best_step()
    if best_step is not None:
        best = ckpts.restore(best_step, template={"params": state.params})
        final_m, final_loss = trainer.evaluate(best["params"], _batch_stream(batcher, val))
        logger.info(
            "best ckpt step=%d: val_loss=%.4f val_F1=%.4f",
            best_step, final_loss, final_m["val_F1Score"],
        )
        last_val = final_m
    with open(tuning_file, "a") as f:
        f.write(json.dumps({"final": True, "val_F1Score": last_val["val_F1Score"]}) + "\n")
    # per-pass routing counters: the last train epoch's and the final val
    # pass's, under distinct keys — "n_dropped must stay 0" is then checked
    # against the corpus the trainer actually consumed, not just val
    last_val = dict(last_val) | route
    last_val["n_rollbacks"] = n_rollbacks
    last_val["lr_scale"] = trainer.lr_scale
    last_val["resharded"] = int(resharded)
    if sentinel is not None:
        last_val |= sentinel.stats()
    journal.write(
        epoch=cfg.optim.max_epochs - 1,
        global_step=int(state.step),
        seed=cfg.seed,
        best_metric=ckpts.best_metric(),
        lr_scale=trainer.lr_scale,
        rollbacks=n_rollbacks,
        mesh=topology,
        resharded=resharded,
        epochs=epoch_rows,
        completed=True,
    )
    atomic_write_text(run_dir / "final_metrics.json", json.dumps(last_val, indent=2))
    if tb is not None:
        tb.close()
    return last_val


def _restore_params(ckpts: CheckpointManager, template_params):
    """Best-else-latest parameter restore — ONE implementation so `test`
    and `predict` can never load different weights for the same run."""
    restored = (
        ckpts.restore_best(template={"params": template_params})
        if ckpts.best_step() is not None
        else ckpts.restore_latest(template={"params": template_params})
    )
    return restored["params"]


def test(
    cfg: ExperimentConfig, run_dir: Path, ckpt_dir: Path | None = None
) -> dict[str, float]:
    corpus = load_corpus(cfg)
    test_graphs = corpus["test"]
    model = make_model(cfg.model, cfg.input_dim)
    trainer = Trainer(model, cfg)
    batcher = _batcher(cfg, test_graphs)
    example = jax.tree.map(jnp.asarray, next(_batch_stream(batcher, test_graphs)))
    state = trainer.init_state(example)

    ckpts = CheckpointManager(ckpt_dir or run_dir / "checkpoints", cfg.checkpoint)
    if ckpts.latest_step() is not None:
        params = _restore_params(ckpts, state.params)
        logger.info("restored checkpoint")
    else:
        params = state.params
        logger.warning("no checkpoint found — evaluating fresh init")

    overall = M.ConfusionState.zeros()
    pos = M.ConfusionState.zeros()
    neg = M.ConfusionState.zeros()
    all_probs, all_labels = [], []
    losses, wsums = [], []
    # node-style runs additionally rank statements per function (IVDetect
    # top-k protocol, ``helpers/evaluate.py:262-322``)
    statement_items: list[tuple[np.ndarray, np.ndarray]] = []
    n_graphs_scored = 0  # must equal len(test_graphs): no silent truncation

    profiler = None
    # FLOPs are a property of (compiled step, batch shapes): the dense
    # primary step, each dense size, and the segment fallback all differ —
    # cache per key, never attribute one step's FLOPs to another's batches
    flops_cache: dict[tuple, float | None] = {}
    if cfg.profile or cfg.time:
        from deepdfa_tpu.train.profiling import StepProfiler

        profiler = StepProfiler(run_dir)

    if cfg.trace:
        jax.profiler.start_trace(str(run_dir / "trace"))
    for batch in _batch_stream(batcher, test_graphs):
        batch = jax.tree.map(jnp.asarray, batch)
        # per-batch step: the primary layout's jitted eval step (shared with
        # fit-time validation — one compile), or the segment fallback for
        # dense-layout oversize overflow batches
        eval_step = trainer.steps_for(batch)[1]
        n_real = int(np.asarray(batch.graph_mask).sum())
        n_graphs_scored += n_real
        if profiler is not None:
            flops = None
            if cfg.profile:
                key = (id(eval_step), tuple(
                    (tuple(x.shape), str(x.dtype)) for x in jax.tree.leaves(batch)
                ))
                if key not in flops_cache:
                    # exact FLOPs of the compiled step, once per (step, shape)
                    # — jit caches the executable, so this lowers-and-looks-up
                    cost = eval_step.lower(params, batch, overall).compile().cost_analysis()
                    flops_cache[key] = (float(cost.get("flops", 0.0)) or None) if cost else None
                flops = flops_cache[key]
            overall, loss, probs, labels, weights = profiler.step(
                eval_step, params, batch, overall, batch_size=n_real, flops=flops
            )
        else:
            overall, loss, probs, labels, weights = eval_step(params, batch, overall)
        pos, neg = M.update_confusion_by_class(pos, neg, probs, labels, weights > 0)
        losses.append(float(loss))
        wsums.append(float(np.asarray(weights).sum()))
        keep = np.asarray(weights) > 0
        all_probs.append(np.asarray(probs)[keep])
        all_labels.append(np.asarray(labels)[keep])
        if cfg.model.label_style == "node":
            p_np, l_np, k_np = np.asarray(probs), np.asarray(labels), keep
            if hasattr(batch, "node_gidx"):  # segment layout: flat nodes
                gidx = np.asarray(batch.node_gidx)
                for gi in range(n_real):
                    sel = (gidx == gi) & k_np
                    if sel.any():
                        statement_items.append((p_np[sel], l_np[sel].astype(int)))
            else:  # dense layout: [G, n] rows are per-graph already
                for gi in range(n_real):
                    sel = k_np[gi]
                    if sel.any():
                        statement_items.append(
                            (p_np[gi][sel], l_np[gi][sel].astype(int))
                        )

    if cfg.trace:
        jax.profiler.stop_trace()
        logger.info("device trace written to %s", run_dir / "trace")

    probs = np.concatenate(all_probs)
    labels = np.concatenate(all_labels)
    results = {"test_loss": _weighted_mean(losses, wsums)}
    results |= _oversize_stats(batcher)
    results["n_graphs_scored"] = n_graphs_scored
    if n_graphs_scored != len(test_graphs):
        logger.warning(
            "scored %d of %d test graphs — the batcher truncated the corpus",
            n_graphs_scored, len(test_graphs),
        )
    results |= M.compute_metrics(overall, "test_")
    results |= M.compute_metrics(pos, "test_pos_")
    results |= M.compute_metrics(neg, "test_neg_")
    results |= {f"report_{k}": v for k, v in M.classification_report(probs, labels).items()}
    if statement_items:
        topk = M.eval_statements_list(statement_items)
        results |= {f"statement_hit@{k}": v for k, v in topk.items()}
        logger.info("statement top-k hit rates: %s",
                    {k: round(v, 4) for k, v in topk.items()})

    import pandas as pd

    p, r, t = M.pr_curve(probs, labels.astype(int))
    pd.DataFrame({"precision": p, "recall": r, "thresholds": t}).to_csv(run_dir / "pr.csv")
    p, r, t = M.binned_pr_curve(probs, labels.astype(int), bins=100)
    pd.DataFrame({"precision": p, "recall": r, "thresholds": t}).to_csv(run_dir / "pr_binned.csv")
    logger.info("confusion matrix:\n%s", M.confusion_matrix(probs, labels))
    logger.info("test metrics: %s", {k: round(v, 4) for k, v in results.items() if k.startswith("test_")})

    if profiler is not None:
        from deepdfa_tpu.train.profiling import report

        profiler.flush()
        prof = report(run_dir)
        results |= {f"profile_{k}": v for k, v in prof.items()}
        logger.info("profiling: %s", prof)

    atomic_write_text(run_dir / "test_metrics.json", json.dumps(results, indent=2))
    return results


# dbize_absdf.py:21-45's feature-variant grid: limit_all values x single
# subkeys (the reference materialises 28 nodes_feat_* variants and its
# analyzer reports coverage for whichever is configured; `analyze` here
# reports the whole grid in one pass)
COVERAGE_GRID_LIMITS = (1, 10, 100, 500, 1000, 5000, 10000)


def coverage(graphs: list[Graph], feat: str = "_ABS_DATAFLOW") -> dict:
    """Feature + dataflow-solution coverage statistics for one split — full
    parity with the reference's per-dataset printout (``get_coverage``,
    ``main_cli.py:192-313``): per-graph def/known/unknown/nodef counts
    aggregated micro (token-weighted) and macro (graph-weighted), the
    graphs-without-defs and has-unknown counts, and — when the shards carry
    the RD solution bits (``--dataflow-labels`` preprocessing) — the
    solution-proportion stats over all nodes and over definition nodes
    (with the NaN accounting for def-free graphs, ``main_cli.py:298-313``)."""
    defs, known, unknown, nodef, nodes = [], [], [], [], []
    vul_nodes = vul_graphs = 0
    skipped_feat = skipped_sol = 0
    prop, prop_nz = [], []
    for g in graphs:
        vul_nodes += int(g.node_feats["_VULN"].sum())
        vul_graphs += int(g.node_feats["_VULN"].max() > 0)
        ids = g.node_feats.get(feat)
        if ids is None:
            skipped_feat += 1
            continue
        nodes.append(ids.size)
        defs.append(int((ids > 0).sum()))
        nodef.append(int((ids == 0).sum()))
        known.append(int((ids > 1).sum()))
        unknown.append(int((ids == 1).sum()))
        sol = g.node_feats.get("_DF_IN")
        if sol is None:
            skipped_sol += 1
        else:
            prop.append(float(np.mean(sol)))
            nz = sol[ids > 0]
            prop_nz.append(float(np.mean(nz)) if nz.size else float("nan"))

    n = np.array(nodes, dtype=float)
    d = np.array(defs, dtype=float)
    k = np.array(known, dtype=float)
    u = np.array(unknown, dtype=float)
    nd = np.array(nodef, dtype=float)
    has_defs = d > 0
    safe = lambda num, den: float(num / den) if den else 0.0

    out: dict = {
        "graphs": len(graphs),
        "graphs_with_features": int(len(d)),
        "skipped_feat": skipped_feat,
        "skipped_sol": skipped_sol,
        "nodes": int(n.sum()),
        "avg_num_nodes": float(n.mean()) if n.size else 0.0,
        "graphs_without_defs": int((~has_defs).sum()),
        "graphs_with_unknown": int((u > 0).sum()),
        "avg_num_nodef": float(nd.mean()) if nd.size else 0.0,
        "avg_num_def": float(d.mean()) if d.size else 0.0,
        "avg_num_known": float(k.mean()) if k.size else 0.0,
        "avg_num_unknown": float(u.mean()) if u.size else 0.0,
        "pct_def_nodes_macro": float(np.mean(d / n)) if n.size else 0.0,
        "pct_nodes_known_micro": safe(k.sum(), n.sum()),
        "pct_nodes_unknown_micro": safe(u.sum(), n.sum()),
        "pct_nodes_known_macro": float(np.mean(k / n)) if n.size else 0.0,
        "pct_nodes_unknown_macro": float(np.mean(u / n)) if n.size else 0.0,
        "pct_def_known_micro": safe(k.sum(), d.sum()),
        "pct_def_unknown_micro": safe(u.sum(), d.sum()),
        "pct_def_known_micro_graphs_with_defs": safe(
            k[has_defs].sum(), d[has_defs].sum()
        ),
        "pct_def_unknown_micro_graphs_with_defs": safe(
            u[has_defs].sum(), d[has_defs].sum()
        ),
        "pct_def_known_macro_graphs_with_defs": (
            float(np.mean(k[has_defs] / d[has_defs])) if has_defs.any() else 0.0
        ),
        "pct_def_unknown_macro_graphs_with_defs": (
            float(np.mean(u[has_defs] / d[has_defs])) if has_defs.any() else 0.0
        ),
        "pct_vul_nodes": safe(vul_nodes, n.sum()),
        "pct_vul_graphs": safe(vul_graphs, len(graphs)),
        # flat aliases kept from the round-2 analyzer (tests/tooling compat)
        "pct_def_nodes": safe(d.sum(), n.sum()),
        "pct_known_defs": safe(k.sum(), d.sum()),
        "pct_unknown_defs": safe(u.sum(), d.sum()),
    }
    if prop:
        pz = np.array(prop_nz, dtype=float)
        valid = pz[~np.isnan(pz)]
        out["solution"] = {
            "avg_proportion_dataflow": float(np.mean(prop)),
            "avg_proportion_definitions_dataflow": (
                float(np.mean(valid)) if valid.size else 0.0
            ),
            "num_proportion_definitions_nan": int(np.isnan(pz).sum()),
            "pct_proportion_definitions_nan": safe(
                int(np.isnan(pz).sum()), len(pz)
            ),
        }
    return out


def variant_coverage(
    hash_df, splits: dict[str, set[int]],
    limits: Sequence[int] = COVERAGE_GRID_LIMITS,
) -> dict[str, dict[str, float]]:
    """Per-feature-variant def coverage over the limit_all x subkey grid
    (the 28 ``nodes_feat_*`` variants of ``dbize_absdf.py:21-45``): for each
    single-subkey vocabulary rebuilt from the TRAIN split at each limit,
    the fraction of definitions per split whose combined hash is known
    (feature id >= 2). Needs the stage-2 hash table persisted by
    ``scripts/preprocess.py`` (``hashes.parquet``)."""
    from deepdfa_tpu.config import ALL_SUBKEYS, FeatureConfig
    from deepdfa_tpu.data.vocab import build_vocab

    # hoist the loop-invariant work out of the 28-cell grid: parse each
    # hash ONCE and slice each split ONCE (on Big-Vul-scale tables the
    # naive loop re-parses and re-scans ~56 times)
    hash_df = hash_df.copy()
    hash_df["hash_dict"] = hash_df["hash"].apply(json.loads)
    split_rows = {
        part: hash_df[hash_df.graph_id.isin(ids)]["hash_dict"]
        for part, ids in splits.items()
    }

    out: dict[str, dict[str, float]] = {}
    train_ids = splits.get("train", set())
    for sk in ALL_SUBKEYS:
        for limit in limits:
            fcfg = FeatureConfig(
                subkeys=(sk,), limit_all=limit, limit_subkeys=limit
            )
            voc = build_vocab(hash_df, train_ids, fcfg)
            stats: dict[str, float] = {}
            for part, dicts in split_rows.items():
                if not len(dicts):
                    stats[part] = 0.0
                    continue
                fids = dicts.apply(voc.feature_id_from_dict)
                stats[part] = float((fids >= 2).mean())
            out[f"{sk}_all_limitall_{limit}_limitsubkeys_{limit}"] = stats
    return out


def predict(
    cfg: ExperimentConfig,
    run_dir: Path,
    sources: Sequence[str],
    ckpt_dir: Path | None = None,
    top_k: int = 5,
    saliency: str = "occlusion",
) -> dict:
    """Scan raw C files with a trained checkpoint: per-function
    vulnerability probability + ranked statements. The end-to-end surface
    the reference lacks (its test path reads preprocessed shards only);
    full pipeline lives in :mod:`deepdfa_tpu.predict`."""
    from deepdfa_tpu.data.graphs import batch_np
    from deepdfa_tpu.predict import load_vocabs, predict_paths

    import dataclasses

    sample_text = "_sample" if cfg.data.sample else ""
    shard_dir = utils.processed_dir() / cfg.data.dsname / f"shards{sample_text}"
    vocabs = load_vocabs(shard_dir)
    # scoring runs one small graph per batch: the segment forward is the
    # right layout, and checkpoints are layout-portable (shared param tree),
    # so a dense-trained checkpoint restores into it unchanged
    if cfg.model.layout != "segment":
        cfg = dataclasses.replace(
            cfg, model=dataclasses.replace(cfg.model, layout="segment"))
    model = make_model(cfg.model, cfg.input_dim)

    # template init on a minimal structurally-valid batch (predict builds
    # its own per-function batches; the checkpoint restore just needs the
    # parameter tree's shape)
    n = 4
    feats: dict[str, np.ndarray] = {"_VULN": np.zeros(n, np.int32)}
    for key in vocabs:
        feats[key] = np.zeros(n, np.int32)
    dummy = Graph(
        senders=np.arange(n - 1, dtype=np.int32),
        receivers=np.arange(1, n, dtype=np.int32),
        node_feats=feats,
    ).with_self_loops()
    example = jax.tree.map(jnp.asarray, batch_np([dummy], 2, 8, 128))
    params = model.init(jax.random.key(0), example)["params"]

    ckpts = CheckpointManager(ckpt_dir or run_dir / "checkpoints", cfg.checkpoint)
    if ckpts.latest_step() is None:
        raise FileNotFoundError(
            f"no checkpoint under {ckpt_dir or run_dir / 'checkpoints'} — "
            "predict scores with a TRAINED model; run fit first"
        )
    params = _restore_params(ckpts, params)

    report = predict_paths(sources, cfg=cfg, model=model, params=params,
                           vocabs=vocabs, top_k=top_k, saliency=saliency)
    atomic_write_text(run_dir / "predictions.json", json.dumps(report, indent=2))
    print(json.dumps(report))
    return report


def export_model(
    cfg: ExperimentConfig, run_dir: Path, ckpt_dir: Path | None = None
) -> dict:
    """Serialize the trained scoring forward to a portable StableHLO
    artifact (``deepdfa_tpu/serving.py``) — params baked in, loadable
    without the model code. Restores best-else-latest exactly like
    ``test``/``predict``."""
    import dataclasses

    from deepdfa_tpu.serving import example_batch, export_ggnn

    # serve the segment forward: checkpoints are layout-portable (shared
    # param tree), and the exported schema is a BatchedGraphs — same
    # coercion predict applies
    if cfg.model.layout != "segment":
        cfg = dataclasses.replace(
            cfg, model=dataclasses.replace(cfg.model, layout="segment"))
    model = make_model(cfg.model, cfg.input_dim)
    example = jax.tree.map(jnp.asarray, example_batch(cfg))
    params = model.init(jax.random.key(0), example)["params"]
    ckpts = CheckpointManager(ckpt_dir or run_dir / "checkpoints", cfg.checkpoint)
    if ckpts.latest_step() is None:
        raise FileNotFoundError(
            f"no checkpoint under {ckpt_dir or run_dir / 'checkpoints'} — "
            "export serializes a TRAINED model; run fit first"
        )
    params = _restore_params(ckpts, params)
    best = ckpts.best_step()
    provenance = {
        "checkpoint_dir": str(ckpt_dir or run_dir / "checkpoints"),
        "restored": ("best" if best is not None else "latest"),
        "step": int(best if best is not None else ckpts.latest_step()),
    }
    # stale-artifact guard: record the training vocab's content hash so a
    # server loading this artifact against different shards gets warned
    vocab_hash = None
    try:
        from deepdfa_tpu.pipeline import load_vocabs, vocab_content_hash

        sample_text = "_sample" if cfg.data.sample else ""
        vocab_hash = vocab_content_hash(load_vocabs(
            utils.processed_dir() / cfg.data.dsname / f"shards{sample_text}"))
    except (FileNotFoundError, ValueError):
        logger.warning("no readable vocab.json under the config's shard dir "
                       "— manifest carries vocab_hash=null")
    out = export_ggnn(cfg, params, run_dir / "export",
                      model=model, example=example, provenance=provenance,
                      vocab_hash=vocab_hash)
    size = (out / "model.stablehlo").stat().st_size
    result = {"export_dir": str(out), "stablehlo_bytes": size, **provenance}
    print(json.dumps(result))
    return result


def analyze(cfg: ExperimentConfig, run_dir: Path) -> dict:
    """The ``--analyze_dataset`` equivalent (``run_analyze_dataset.sh`` /
    ``get_coverage``): per-split feature+solution coverage at the
    materialised config, the vul distribution, and — when the hash table
    was persisted — the full per-feature-variant coverage grid. Writes
    ``coverage.json`` (a superset of the reference's printout)."""
    corpus = load_corpus(cfg)
    out: dict = {"splits": {}}
    n_vul = {p: sum(int(g.node_feats["_VULN"].max() > 0) for g in gs)
             for p, gs in corpus.items()}
    out["vul_distribution"] = {
        p: {"vul": n_vul[p], "nonvul": len(gs) - n_vul[p], "total": len(gs)}
        for p, gs in corpus.items()
    }
    for part, graphs in corpus.items():
        stats = coverage(graphs)
        logger.info(
            "%s coverage: %s", part,
            {k: round(v, 4) if isinstance(v, float) else v
             for k, v in stats.items() if not isinstance(v, dict)},
        )
        out["splits"][part] = stats

    sample_text = "_sample" if cfg.data.sample else ""
    shard_dir = utils.processed_dir() / cfg.data.dsname / f"shards{sample_text}"
    hash_path = shard_dir / "hashes.parquet"
    csv_path = shard_dir / "hashes.csv.gz"
    splits_file = shard_dir / "splits.json"
    if (hash_path.exists() or csv_path.exists()) and splits_file.exists():
        import pandas as pd

        hash_df = (pd.read_parquet(hash_path) if hash_path.exists()
                   else pd.read_csv(csv_path))
        splits = {k: set(v) for k, v in json.loads(splits_file.read_text()).items()}
        out["variants"] = variant_coverage(hash_df, splits)
        for name, stats in out["variants"].items():
            logger.info("variant %s: %s", name,
                        {k: round(v, 4) for k, v in stats.items()})
    else:
        out["variants"] = None
        logger.info("no hashes.parquet under %s — variant grid skipped "
                    "(re-run scripts/preprocess.py to persist it)", shard_dir)

    atomic_write_text(run_dir / "coverage.json", json.dumps(out, indent=2))
    return out


# ---------------------------------------------------------------------------
# entry


def _parse_overrides(pairs: Sequence[str]) -> dict:
    out = {}
    for pair in pairs:
        key, _, value = pair.partition("=")
        try:
            out[key] = json.loads(value)
        except json.JSONDecodeError:
            out[key] = value
    return out


def trace_export(src: Path, out: Path | None = None) -> dict:
    """Collect ``event=trace`` exemplar records under ``src`` (a run dir,
    a trace dir, or one file) into ONE Chrome trace-event JSON — open it
    in Perfetto / ``chrome://tracing``."""
    from deepdfa_tpu.obs import chrome_trace, load_trace_records

    records = load_trace_records(src)
    spans = [s for rec in records for s in rec.get("spans", [])]
    trace = chrome_trace(spans)
    if out is None:
        out = (src / "trace_events.json" if src.is_dir()
               else src.with_suffix(".chrome.json"))
    atomic_write_text(Path(out), json.dumps(trace, indent=2))
    summary = {"trace_records": len(records), "spans": len(spans),
               "out": str(out)}
    print(json.dumps(summary), flush=True)
    return summary


def main(argv: Sequence[str] | None = None) -> dict:
    parser = argparse.ArgumentParser(prog="deepdfa-tpu")
    parser.add_argument("command",
                        choices=["fit", "test", "analyze", "predict",
                                 "export", "serve", "trace", "bench", "scan"])
    parser.add_argument("subcommand", nargs="?", default=None,
                        help="trace: 'export' (the default) — merge a run "
                        "dir's trace exemplars into Chrome trace-event JSON; "
                        "bench: 'ledger' (the default) — perf-regression "
                        "verdicts over the repo's bench artifacts; "
                        "scan: the repo/dir/file to walk (or use --source)")
    parser.add_argument("--out", default=None,
                        help="trace export: output path (default: "
                        "<run-dir>/trace_events.json)")
    parser.add_argument("--config", action="append", default=[],
                        help="layered config files (later files win)")
    parser.add_argument("--set", action="append", default=[], dest="overrides",
                        help="dotted overrides, e.g. --set optim.max_epochs=3")
    parser.add_argument("--run-dir", default=None)
    parser.add_argument("--resume", action="store_true",
                        help="fit: resume from the run dir's latest good "
                        "checkpoint + journal (fresh run if none found)")
    parser.add_argument("--ckpt-dir", default=None,
                        help="checkpoint dir for test/predict/export")
    parser.add_argument("--source", action="append", default=[],
                        help="predict/scan: C file or directory (repeatable)")
    parser.add_argument("--workers", type=int, default=4,
                        help="scan: extraction-pool worker count")
    parser.add_argument("--cache-dir", default=None,
                        help="scan: extraction-cache dir (default: "
                        "<run-dir>/extract_cache)")
    parser.add_argument("--top-k", type=int, default=5,
                        help="predict: statements ranked per function")
    parser.add_argument("--artifact", default=None,
                        help="serve: pre-exported StableHLO artifact dir "
                        "(deepdfa-tpu export) instead of a checkpoint")
    parser.add_argument("--check", action="store_true",
                        help="bench ledger: exit non-zero when the latest "
                        "entry of any series regressed past its band")
    parser.add_argument("--trend", action="store_true",
                        help="bench ledger: print per-series sparkline trends")
    parser.add_argument("--ledger-dir", action="append", default=[],
                        help="bench ledger: artifact file or directory to "
                        "ingest (repeatable; default: CWD)")
    parser.add_argument("--cascade", action="store_true",
                        help="scan: rescore borderline-band functions "
                        "through the tier-2 joint engine (needs "
                        "serve.cascade.joint_dir); rows record the "
                        "answering tier and the tier-1 score")
    parser.add_argument("--interproc", action="store_true",
                        help="scan: additionally score the target as ONE "
                        "unit — merge every file's CPG, build the call-"
                        "graph supergraph, and report cross-function taint "
                        "flows (source API in the caller, sink in the "
                        "callee) with per-function attribution in "
                        "scan.json['interproc']")
    parser.add_argument("--saliency", choices=("occlusion", "gate"),
                        default="occlusion",
                        help="predict statement ranking: occlusion = per-"
                        "statement evidence drop (default; 12/12 top-1 on "
                        "the demo localization study vs the gate's 0/12 — "
                        "BASELINE.md); gate = readout attention, 1 forward")
    args = parser.parse_args(argv)
    utils.setup_compile_cache()
    if args.command == "predict" and not args.source:
        parser.error("predict requires at least one --source")
    if args.command == "scan" and not (args.subcommand or args.source):
        parser.error("scan requires a target path (positional or --source)")
    if args.command == "trace":
        # a reporting path: no config load, no run-dir creation, no logging
        # re-init — it must work against a finished (or foreign) run dir
        if (args.subcommand or "export") != "export":
            parser.error(f"unknown trace subcommand {args.subcommand!r}")
        if not args.run_dir:
            parser.error("trace export requires --run-dir")
        return trace_export(Path(args.run_dir),
                            Path(args.out) if args.out else None)
    if args.command == "bench":
        # like trace: a reporting path — no config load, no run-dir
        # creation, no logging re-init. Works from any checkout with
        # bench artifacts lying around (CI gates call it headless).
        if (args.subcommand or "ledger") != "ledger":
            parser.error(f"unknown bench subcommand {args.subcommand!r}")
        from deepdfa_tpu.obs import ledger

        ledger_argv = list(args.ledger_dir)
        if args.check:
            ledger_argv.append("--check")
        if args.trend:
            ledger_argv.append("--trend")
        rc = ledger.main(ledger_argv)
        if rc:
            raise SystemExit(rc)
        return {"command": "bench", "subcommand": "ledger", "rc": rc}

    layers = list(args.config)
    if args.command in ("predict", "export", "serve", "scan") and args.run_dir:
        # score with the RUN'S OWN recorded config as the base layer (CLI
        # configs/overrides still win): `predict --run-dir <fit dir>` must
        # restore a non-default-trained checkpoint without the caller
        # re-passing every fit-time override
        saved = Path(args.run_dir) / "config.json"
        if saved.exists():
            layers.insert(0, saved)
    cfg = load_config(*layers, overrides=_parse_overrides(args.overrides))
    utils.seed_all(cfg.seed)

    run_id = cfg.run_name or utils.get_run_id([args.command])
    run_dir = Path(args.run_dir) if args.run_dir else utils.get_dir(
        utils.storage_dir() / "runs" / run_id
    )
    run_dir.mkdir(parents=True, exist_ok=True)
    log_file = run_dir / "run.log"
    handlers = [logging.StreamHandler(sys.stderr), logging.FileHandler(log_file)]
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
        handlers=handlers,
        force=True,
    )
    from deepdfa_tpu.config import to_json

    if (args.command not in ("predict", "export", "serve", "scan")
            or not (run_dir / "config.json").exists()):
        # no-clobber for predict: it is routinely pointed AT a fit run dir
        # (README usage) and must not overwrite the trained run's recorded
        # config — but a FRESH predict run dir still gets provenance
        atomic_write_text(run_dir / "config.json", to_json(cfg))
    where = utils.describe_backend()
    logger.info("run %s: %s backend=%s device_kind=%r devices=%d",
                run_id, args.command, where["backend"],
                where["device_kind"], where["device_count"])

    try:
        if args.command == "fit":
            return fit(cfg, run_dir, resume=args.resume)
        if args.command == "test":
            return test(cfg, run_dir, Path(args.ckpt_dir) if args.ckpt_dir else None)
        if args.command == "predict":
            return predict(cfg, run_dir, args.source,
                           Path(args.ckpt_dir) if args.ckpt_dir else None,
                           top_k=args.top_k, saliency=args.saliency)
        if args.command == "export":
            return export_model(
                cfg, run_dir,
                Path(args.ckpt_dir) if args.ckpt_dir else None)
        if args.command == "serve":
            from deepdfa_tpu.serve.server import serve_command

            return serve_command(
                cfg, run_dir=run_dir,
                ckpt_dir=Path(args.ckpt_dir) if args.ckpt_dir else None,
                artifact=args.artifact)
        if args.command == "scan":
            from deepdfa_tpu.scan import scan_command

            targets = ([args.subcommand] if args.subcommand else []) + list(
                args.source)
            return scan_command(
                cfg, run_dir, targets,
                ckpt_dir=Path(args.ckpt_dir) if args.ckpt_dir else None,
                artifact=args.artifact, workers=args.workers,
                cache_dir=Path(args.cache_dir) if args.cache_dir else None,
                cascade=args.cascade, interproc=args.interproc)
        return analyze(cfg, run_dir)
    except Exception:
        # crash marker parity: rename log to .log.error (main_cli.py:324-336).
        # NOT for predict: it is routinely pointed at a fit run dir, and a
        # failed scan must not mark the completed TRAINING run as crashed.
        for h in handlers:
            h.close()
        if args.command not in ("predict", "export", "serve", "scan"):
            log_file.rename(log_file.with_suffix(".log.error"))
        raise


if __name__ == "__main__":
    main()
