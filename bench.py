"""Benchmark: flagship GGNN throughput on the local accelerator — self-validating.

Runs on the chip or not at all: the backend must be the TPU (or the
platform ``JAX_PLATFORMS`` pins), the chip's ``device_kind`` must have a
peak in ``NOMINAL_BF16_TFLOPS``, and a stage that raises ends the run with
a non-zero exit code. There is no CPU fallback and no replay of old
artifacts.

Prints ONE JSON line:
``{"metric": ..., "value": N, "unit": "graphs/sec", "vs_baseline": N, ...}``.

Headline metric: **GGNN inference graphs/sec under the chained protocol** at
the reference's golden config (hidden 32, 5 steps, concat_all_absdf, batch
256 graphs) on Big-Vul-shaped synthetic batches (mean ~50 CFG nodes/function;
the real corpus needs a network download the bench environment doesn't have).
Bucket budgets are derived from the corpus (``data/graphs.derive_buckets``)
so the number is quoted on real graphs, not padding — ``padding_efficiency``
is reported.

**Chained protocol** (round-3 redesign): ``k`` device-resident batches are
processed by ONE jitted ``lax.scan`` whose carry accumulates a scalar that
depends on every step's output, timed with a strict device→host readback of
that scalar. This is impossible to fake (the readback value requires all k
steps) and amortises the per-dispatch host↔device round trip. The
single-dispatch strict number is still reported (``strict_graphs_per_sec``)
alongside.

Every throughput number self-validates against physics, in-process:

- ``flops_per_step`` comes from the compiled computation's ``cost_analysis()``;
- ``roofline_tflops`` is parallel independent bf16 matmul chains measured in
  the same process (the MXU ceiling actually reachable right now); ``mfu``
  is the fraction of it, ``mfu_nominal`` uses the chip's datasheet peak —
  a device kind without one is an error, not a null column.
- each metric's implied FLOP/s must be ≤ the roofline or the metric is
  REFUSED (reported as null with the reason in ``refused``). A throughput
  that beats the hardware ceiling is a timing artifact, not throughput.

``vs_baseline``: ratio against a **same-semantics torch-CPU implementation**
(``deepdfa_tpu/compat/torch_ref.py``) measured in-process. The reference's own
GPU harness (DGL + CUDA events, ``base_module.py:246-281``) cannot run here —
no CUDA and no DGL wheel. ``est_vs_a100`` derives the north-star ratio
(BASELINE.json: ≥8× vs 1×A100) as measured graphs/sec ÷ (A100 bf16 peak ×
assumed MFU ÷ FLOPs/graph); the assumption is printed alongside.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


def _progress(msg: str) -> None:
    """Stage markers on stderr: the chip tool shows nothing while the
    command runs and only the tail afterwards — the markers say which stage
    a killed or failed run had reached."""
    print(f"[bench +{time.monotonic() - _T0:.1f}s] {msg}", file=sys.stderr, flush=True)


_T0 = time.monotonic()


def start_on_device() -> tuple[str, str]:
    """First call of every bench script's ``main``: place the compile cache,
    then state — and check — where the run is. Returns ``(backend,
    device_kind)``. Anything but the TPU raises unless ``JAX_PLATFORMS``
    pinned that platform (``utils.require_backend``): JAX itself drops to
    CPU with only a warning when libtpu fails to initialise."""
    from deepdfa_tpu import utils

    utils.setup_compile_cache()
    where = utils.require_backend()
    _progress(f"backend={where['backend']} "
              f"device_kind={where['device_kind']!r} "
              f"devices={where['device_count']}")
    return where["backend"], where["device_kind"]

A100_BF16_PEAK_TFLOPS = 312.0
A100_ASSUMED_MFU = 0.40  # generous to the baseline: real GNN MFU on GPU is far lower

# Datasheet bf16 peak for mfu_nominal, keyed by the ``device_kind`` the chip
# reports (single chip). One entry: the installation's TPU v5e, 197 TFLOP/s
# (Google Cloud documentation, "TPU v5e"). A kind that is not here is an
# error (``_nominal_peak_tflops``) — add the row with its source.
NOMINAL_BF16_TFLOPS = {
    "TPU v5 lite": 197.0,
}


def build_corpus(n_graphs: int, input_dim: int):
    """ONE synthetic Big-Vul-shaped corpus per bench run — every layout and
    batch size packs (a prefix of) the same graphs, so segment-vs-dense and
    batch-size comparisons are apples-to-apples by construction."""
    from deepdfa_tpu.data.synthetic import random_dataset

    return random_dataset(n_graphs, seed=0, input_dim=input_dim)


def build_batches(corpus, n_batches: int, batch_graphs: int = 256):
    """Corpus-derived buckets; keep only batches of the main (largest) bucket
    shape so one compiled shape is timed at near-full occupancy."""
    from deepdfa_tpu.data.graphs import GraphBatcher, derive_buckets, padding_efficiency

    graphs = corpus[: int(n_batches * batch_graphs * 1.5)]
    buckets = derive_buckets(graphs, batch_graphs)
    main = buckets[-1]
    batcher = GraphBatcher(buckets)
    batches = []
    for b in batcher.batches(graphs):
        if b.max_nodes == main.max_nodes:
            batches.append(b)
        if len(batches) == n_batches:
            break
    if not batches:
        raise RuntimeError(
            f"no main-bucket batches produced for batch_graphs={batch_graphs} "
            f"(corpus {len(graphs)} graphs, main bucket {main})"
        )
    return batches, padding_efficiency(batches)


def _sync(x) -> float:
    """Hard synchronisation: read a value back to the host. The timed
    scalar depends on the whole computation, so a device→host readback of
    it cannot return before the work is done."""
    import jax

    leaf = jax.tree.leaves(x)[0]
    return float(np.asarray(leaf).ravel()[0])


def _timed(run_once, steps: int):
    """Strict per-step readback-sync timing. Returns (median_s, pipelined_s).

    ``run_once`` must return a SMALL array/scalar whose value depends on the
    whole computation; each timed step transfers it to the host."""
    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        _sync(run_once())
        times.append(time.perf_counter() - t0)
    t0 = time.perf_counter()
    out = None
    for _ in range(steps):
        out = run_once()
    _sync(out)
    pipelined = (time.perf_counter() - t0) / steps
    return float(np.median(times)), pipelined


def _cost_flops(jitted, *args) -> float | None:
    """FLOPs of the compiled computation via XLA's cost analysis; ``None``
    only when the analysis itself reports no ``flops`` entry (a failing
    lower/compile propagates — it would fail the timed run too)."""
    ca = jitted.lower(*args).compile().cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    flops = (ca or {}).get("flops")
    return None if flops is None else float(flops)


def measure_roofline(n_chain: int | None = None, dim: int | None = None,
                     trials: int = 4, n_par: int = 2) -> float:
    """Best-case bf16 matmul FLOP/s reachable in this process right now:
    ``n_par`` INDEPENDENT chains of ``n_chain`` dependent two-matmul hops
    (``acc @ w1 @ w2``, weights stationary) inside one jit, strict readback
    sync, best of ``trials``. This is the ceiling every reported throughput
    is checked against.

    Round-3 redesign: a single serialized dim³ chain measured only ~39% of
    the v5e's nominal peak (each matmul stalls the MXU pipeline on its
    predecessor), so the honest LLM bench — 65% MFU on dense decoder
    matmuls — was refused against a ceiling the probe itself couldn't
    reach. Independent parallel chains keep the pipeline full, making the
    refusal gate a true upper bound instead of a 2.2×-too-low one."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    dim = dim or 8192
    n_chain = n_chain or 32

    x = jnp.ones((n_par, dim, dim), jnp.bfloat16) * 1e-2
    w1 = jax.random.normal(jax.random.key(0), (n_par, dim, dim), jnp.bfloat16) * (dim ** -0.5)
    w2 = jax.random.normal(jax.random.key(1), (n_par, dim, dim), jnp.bfloat16) * (dim ** -0.5)

    @jax.jit
    def chain(x, w1, w2):
        def body(i, acc):
            h = jnp.einsum("bmk,bkn->bmn", acc, w1,
                           preferred_element_type=jnp.bfloat16)
            return jnp.einsum("bmn,bnk->bmk", h, w2,
                              preferred_element_type=jnp.bfloat16)
        acc = lax.fori_loop(0, n_chain, body, x)
        return jnp.sum(acc.astype(jnp.float32))  # scalar out → cheap readback sync

    _sync(chain(x, w1, w2))  # compile + warm
    best = min(_time_once(lambda: _sync(chain(x, w1, w2))) for _ in range(trials))
    return 2.0 * dim ** 3 * 2 * n_chain * n_par / best


def _time_once(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _stack_tiled(batches, k: int):
    """Stack the distinct batches once (one host→device transfer each), then
    tile to ``k`` scan steps ON DEVICE via a cycling gather instead of
    transferring the same host batch k/len(batches) times. Distinct data
    per step — XLA cannot CSE across scan iterations."""
    import jax
    import jax.numpy as jnp

    idx = np.arange(k) % len(batches)
    stacked = jax.tree.map(lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]),
                           *batches)
    return jax.tree.map(lambda x: jnp.take(x, idx, axis=0), stacked)


def _time_chained_inference(apply_fn, params, batches, k: int, trials: int = 3):
    """Shared chained-protocol inference timing for BOTH graph layouts: one
    jitted ``lax.scan`` over a cycling batch index whose scalar readback
    depends on every step. The distinct batches are device-resident ONCE
    (len(batches) copies, k-independent memory — tiling k copies of a dense
    adjacency stack would cost GBs); the scan body gathers batch ``i``, so
    data still varies per step and XLA cannot hoist loop-invariant work.
    Returns best-of-``trials`` wall seconds for the whole k-chain."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    stacked = jax.tree.map(lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]),
                           *batches)
    idx = jnp.asarray(np.arange(k) % len(batches), jnp.int32)

    @jax.jit
    def chained(params, stacked, idx):
        def body(acc, i):
            batch = jax.tree.map(lambda x: x[i], stacked)
            logits = apply_fn(params, batch)
            return acc + jnp.sum(logits.astype(jnp.float32)), None

        acc, _ = lax.scan(body, jnp.zeros((), jnp.float32), idx)
        return acc

    _sync(chained(params, stacked, idx))  # compile + warm
    return min(
        _time_once(lambda: _sync(chained(params, stacked, idx)))
        for _ in range(trials)
    )


def build_dense_batches(corpus, n_batches: int, batch_graphs: int = 256):
    """Dense-adjacency batches over the same corpus prefix as
    :func:`build_batches`, size-bucketed by the optimal k-bucket DP
    (``derive_dense_sizes``, default k=6 — slot cost scales n², and the DP
    split reached 0.83 node occupancy vs the old {p50,p99} pair's 0.49 on
    this corpus, at up to 6 compiled shapes). Returns
    (groups, occupancy, n_dropped): ``groups`` maps nodes_per_graph → up to
    ``n_batches`` full batches of that compiled shape."""
    from deepdfa_tpu.data.dense import DenseBatcher, derive_dense_sizes

    # optimal k-bucket split (round-5: replaces the {p50,p99} heuristic —
    # VERDICT r04 #2 occupancy push)
    sizes = derive_dense_sizes(corpus[: int(n_batches * batch_graphs * 1.5)])
    # the stream splits across len(sizes) buckets — scale the slice so each
    # bucket can still fill n_batches full batches
    graphs = corpus[: int(n_batches * batch_graphs * 1.5 * len(sizes))]
    batcher = DenseBatcher(max_graphs=batch_graphs, nodes_per_graph=sizes)
    groups: dict[int, list] = {}
    for b in batcher.batches(graphs, limit_per_size=n_batches):
        groups.setdefault(b.nodes_per_graph, []).append(b)
    if not groups:
        raise RuntimeError(f"no full dense batches (sizes={sizes})")
    all_batches = [b for g in groups.values() for b in g]
    return groups, batcher.occupancy(all_batches), batcher.n_dropped


def bench_chained_dense(groups, k: int, dtype: str = "bfloat16", trials: int = 3,
                        on_shape=None):
    """Chained protocol over the dense-adjacency forward (shared timing
    helper — identical protocol to the segment layout by construction).

    ``groups`` maps nodes_per_graph → batches of that compiled shape. Each
    shape gets its own chained scan with ``k`` split ∝ how much of the
    corpus that shape carries; the quoted rate is the mixture
    ``Σ graphs / Σ wall`` — large-graph batches are NOT quietly skipped.
    ``flops_per_step`` is the k-weighted mean so the roofline gate checks
    the same mixture it validates.

    ``on_shape(by_shape)`` fires after EVERY shape finishes with the
    per-shape rates measured so far (progress reporting: each shape is its
    own long compile). Per-shape rates are DIAGNOSTIC (never a headline:
    quoting a partial mixture would silently drop the large-graph shapes
    and inflate the rate)."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp

    from deepdfa_tpu.config import ExperimentConfig
    from deepdfa_tpu.models.ggnn_dense import GGNNDense

    cfg = ExperimentConfig()
    cfg = _dc.replace(cfg, model=_dc.replace(cfg.model, dtype=dtype))
    model = GGNNDense(cfg=cfg.model, input_dim=cfg.input_dim)
    apply_fn = lambda p, b: model.apply({"params": p}, b)

    weights = {s: len(g) for s, g in groups.items()}
    total_w = sum(weights.values())
    ks = {s: max(round(k * w / total_w), 1) for s, w in weights.items()}

    total_graphs = total_wall = total_flops = 0.0
    flops_unknown = False
    params = None
    by_shape: dict[str, dict] = {}
    for s, batches in sorted(groups.items()):
        dev0 = jax.tree.map(jnp.asarray, batches[0])
        if params is None:
            params = jax.jit(lambda: model.init(jax.random.key(0), dev0)["params"])()
        real = float(np.mean([int(b.graph_mask.sum()) for b in batches]))
        flops = _cost_flops(jax.jit(apply_fn), params, dev0)
        wall = _time_chained_inference(apply_fn, params, batches, ks[s], trials)
        total_graphs += ks[s] * real
        total_wall += wall
        if flops is None:
            # zeroing would understate the mixture and weaken the roofline
            # refusal gate — propagate None so the gate visibly skips
            flops_unknown = True
        else:
            total_flops += flops * ks[s]
        by_shape[str(s)] = {
            "graphs_per_sec": round(ks[s] * real / wall, 1),
            "step_ms": round(wall / ks[s] * 1e3, 3),
            "k": ks[s],
            "flops_per_step": flops,
        }
        if on_shape is not None:
            on_shape(dict(by_shape))
    k_total = sum(ks.values())
    return {
        "graphs_per_sec": total_graphs / total_wall,
        "step_ms": total_wall / k_total * 1e3,
        "flops_per_step": None if flops_unknown else total_flops / k_total,
        "wall_s": total_wall,
        "k": k_total,
        "graphs_per_step": total_graphs / k_total,
        "shapes": {str(s): ks[s] for s in sorted(groups)},
        "by_shape": by_shape,
    }


def _setup_model(dtype: str, layout: str = "segment"):
    import dataclasses

    from deepdfa_tpu.config import ExperimentConfig
    from deepdfa_tpu.models import make_model
    from deepdfa_tpu.train.loop import Trainer

    cfg = ExperimentConfig()
    cfg = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, dtype=dtype, layout=layout))
    model = make_model(cfg.model, input_dim=cfg.input_dim)
    trainer = Trainer(model=model, cfg=cfg, pos_weight=15.0)
    return model, trainer


def bench_chained(batches, k: int, train: bool, dtype: str = "bfloat16",
                  trials: int = 3, layout: str = "segment"):
    """The headline protocol: ONE jitted ``lax.scan`` over ``k`` device-
    resident batches; the returned scalar depends on every step (inference:
    running sum of all logits; training: final loss + parameter checksum
    after k optimizer updates), so the readback forces the full chain.

    Returns ``{graphs_per_sec, step_ms, flops_per_step, wall_s}`` quoting
    REAL (mask-counted) graphs/sec."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from deepdfa_tpu.train.metrics import ConfusionState

    model, trainer = _setup_model(dtype, layout=layout)
    dev0 = jax.tree.map(jnp.asarray, batches[0])
    state = trainer.init_state(dev0)
    real_graphs = float(np.mean([int(b.graph_mask.sum()) for b in batches]))

    # FLOPs per step come from the SINGLE-step compiled computation:
    # cost_analysis() on a scanned loop counts the body once regardless of
    # trip count, so analysing the chained fn and dividing by k would
    # under-report by ~k× and neuter the roofline refusal gate.
    if train:
        stacked = _stack_tiled(batches, k)
        step = trainer.train_step  # nested jit inlines under trace
        metrics0 = ConfusionState.zeros()
        flops_step = _cost_flops(step, state, dev0, metrics0)

        @jax.jit
        def chained(state, stacked):
            def body(carry, batch):
                st, m = carry
                st, m, loss, _w = step(st, batch, m)
                return (st, m), loss

            (st, m), losses = lax.scan(body, (state, ConfusionState.zeros()), stacked)
            # checksum touches every updated param: the optimizer chain and
            # every backward pass must actually have run
            checksum = sum(
                jnp.sum(p.astype(jnp.float32)) for p in jax.tree.leaves(st.params)
            )
            return jnp.sum(losses) + 0.0 * checksum, st

        _sync(chained(state, stacked))  # compile + warm
        wall = min(
            _time_once(lambda: _sync(chained(state, stacked)))
            for _ in range(trials)
        )
    else:
        apply_fn = lambda p, b: model.apply({"params": p}, b)
        flops_step = _cost_flops(jax.jit(apply_fn), state.params, dev0)
        wall = _time_chained_inference(apply_fn, state.params, batches, k, trials)
    return {
        "graphs_per_sec": k * real_graphs / wall,
        "step_ms": wall / k * 1e3,
        "flops_per_step": flops_step,
        "wall_s": wall,
        "k": k,
    }


def bench_jax(batches, steps: int, train: bool, dtype: str = "bfloat16"):
    """Single-dispatch reference numbers: strict per-step readback sync
    (pays the full host↔device RTT every step — the honest latency a
    one-batch-at-a-time caller sees) plus the dispatch-all pipelined rate."""
    import jax
    import jax.numpy as jnp

    from deepdfa_tpu.train.metrics import ConfusionState

    model, trainer = _setup_model(dtype)
    dev_batches = [jax.tree.map(jnp.asarray, b) for b in batches]
    state = trainer.init_state(dev_batches[0])
    real_graphs = float(np.mean([int(b.graph_mask.sum()) for b in batches]))

    if train:
        step = trainer.train_step
        metrics = ConfusionState.zeros()
        state, metrics, loss, w = step(state, dev_batches[0], metrics)  # compile
        jax.block_until_ready(loss)
        flops = _cost_flops(step, state, dev_batches[0], metrics)
        box = {"state": state, "metrics": metrics, "i": 0}

        def run_once():
            b = dev_batches[box["i"] % len(dev_batches)]
            box["i"] += 1
            box["state"], box["metrics"], loss, _ = step(box["state"], b, box["metrics"])
            return loss

        median_s, pipelined_s = _timed(run_once, steps)
    else:
        fwd = jax.jit(lambda p, b: model.apply({"params": p}, b))
        jax.block_until_ready(fwd(state.params, dev_batches[0]))  # compile
        flops = _cost_flops(fwd, state.params, dev_batches[0])
        box = {"i": 0}

        def run_once():
            b = dev_batches[box["i"] % len(dev_batches)]
            box["i"] += 1
            return fwd(state.params, b)

        median_s, pipelined_s = _timed(run_once, steps)

    return {
        "graphs_per_sec": real_graphs / median_s,
        "pipelined_graphs_per_sec": real_graphs / pipelined_s,
        "flops_per_step": flops,
        "step_ms": median_s * 1e3,
    }


def sentinel_overhead_pct(plain_s: float, guarded_s: float) -> float:
    """Relative per-step cost of the in-jit divergence-sentinel guard, in
    percent. Negative = guard measured faster (timing noise)."""
    if plain_s <= 0:
        raise ValueError(f"plain_s must be > 0, got {plain_s}")
    return (guarded_s - plain_s) / plain_s * 100.0


def sentinel_guard_ok(pct: float, budget: float = 2.0) -> bool:
    """The resilience invariant (ROADMAP): the sentinel's isfinite-and-select
    guard must cost < ``budget`` percent of a training step."""
    return pct <= budget


SERVE_MIN_OCCUPANCY = 0.5


def assemble_serve_result(backend, device_kind, requests_per_sec, p50_ms,
                          p99_ms, mean_batch_occupancy, cache_hit_rate,
                          cache_hits, requests_total, errors_total,
                          concurrency=None, notes=None, fleet=None,
                          autoscale=None, cascade=None, frontend=None,
                          admission=None, federation=None):
    """ONE-line artifact for the serving stage (scripts/bench_serving.py).

    Shared between the load generator and the bench-contract test so the
    schema is asserted without standing up a server. ``ok`` encodes the
    serving acceptance gates: every request answered, batches at least
    half-full on average (the micro-batcher actually coalesced — a 1-deep
    "batch" per request would pass a pure throughput check), and the
    repeated-corpus phase produced real cache hits (asserted via the hit
    COUNTER, not timing). ``fleet`` (an ``assemble_fleet_result`` block,
    from ``--fleet N`` runs), ``autoscale`` (an
    ``assemble_autoscale_result`` block, from ``--autoscale`` runs) and
    ``cascade`` (an ``assemble_cascade_result`` block, from ``--cascade``
    runs) and ``frontend`` (an ``assemble_frontend_result`` block, from
    ``--frontend`` runs) and ``admission`` (an
    ``assemble_admission_result`` block, from ``--overload`` runs) and
    ``federation`` (an ``assemble_federation_result`` block, from
    ``--federation N`` runs) ride along and AND their own ok."""
    ok = (requests_total > 0 and errors_total == 0
          and requests_per_sec > 0
          and mean_batch_occupancy is not None
          and mean_batch_occupancy >= SERVE_MIN_OCCUPANCY
          and cache_hits > 0)
    if fleet is not None:
        ok = ok and bool(fleet.get("ok"))
    if autoscale is not None:
        ok = ok and bool(autoscale.get("ok"))
    if cascade is not None:
        ok = ok and bool(cascade.get("ok"))
    if frontend is not None:
        ok = ok and bool(frontend.get("ok"))
    if admission is not None:
        ok = ok and bool(admission.get("ok"))
    if federation is not None:
        ok = ok and bool(federation.get("ok"))
    return {
        "metric": "serve_requests_per_sec",
        "value": round(float(requests_per_sec), 2),
        "unit": "req/s",
        "vs_baseline": None,
        "backend": backend,
        "device_kind": device_kind,
        "p50_ms": None if p50_ms is None else round(float(p50_ms), 3),
        "p99_ms": None if p99_ms is None else round(float(p99_ms), 3),
        "mean_batch_occupancy": (
            None if mean_batch_occupancy is None
            else round(float(mean_batch_occupancy), 4)),
        "min_occupancy": SERVE_MIN_OCCUPANCY,
        "cache_hit_rate": (
            None if cache_hit_rate is None
            else round(float(cache_hit_rate), 4)),
        "cache_hits": int(cache_hits),
        "requests_total": int(requests_total),
        "errors_total": int(errors_total),
        "concurrency": concurrency,
        "notes": notes or {},
        "fleet": fleet,
        "autoscale": autoscale,
        "cascade": cascade,
        "frontend": frontend,
        "admission": admission,
        "federation": federation,
        "ok": ok,
        **_provenance_fields(),
    }


# cascade gates: the bench pre-scores its corpus through the tier-1 engine
# and places the band at known score quantiles, so the expected escalation
# fraction is the band's exact mass — the measured fraction must land
# within ±20% of it (routing, not luck). Nominal load must produce ZERO
# degraded answers (invariant 24 covers failure; the bench covers the
# absence of failure), and the cascade may not tax confident traffic:
# tier-1 p50 regresses < 10% against the no-cascade baseline phase.
CASCADE_ESCALATION_TOL = 0.20
CASCADE_MAX_T1_P50_REGRESSION = 0.10


def assemble_cascade_result(backend, device_kind, band, expected_frac,
                            escalated_total, answered_tier2, degraded_total,
                            requests_total, tier1_p50_ms, baseline_p50_ms,
                            tier2_p50_ms, tier2_p99_ms, errors_total,
                            notes=None):
    """ONE-line ``cascade`` block for ``bench_serving.py --cascade``.

    ``expected_frac`` is the analytically expected band mass (the fraction
    of the pre-scored corpus whose tier-1 score falls inside ``band``);
    ``tier1_p50_ms`` / ``baseline_p50_ms`` are the same load with and
    without the cascade enabled. Gates: escalation fraction within
    ``CASCADE_ESCALATION_TOL`` of expected, every escalation answered by
    tier 2 (``degraded_total == 0`` nominal), zero errors, and tier-1 p50
    within ``CASCADE_MAX_T1_P50_REGRESSION`` of the baseline phase."""
    escalated_frac = (None if not requests_total
                      else float(escalated_total) / float(requests_total))
    escalation_ok = (expected_frac is not None and expected_frac > 0
                     and escalated_frac is not None
                     and abs(escalated_frac - expected_frac)
                     <= CASCADE_ESCALATION_TOL * expected_frac)
    t1_regression_ok = (baseline_p50_ms is not None and baseline_p50_ms > 0
                        and tier1_p50_ms is not None
                        and float(tier1_p50_ms) <= float(baseline_p50_ms)
                        * (1.0 + CASCADE_MAX_T1_P50_REGRESSION))
    ok = (requests_total > 0 and errors_total == 0
          and degraded_total == 0
          and int(answered_tier2) == int(escalated_total)
          and escalation_ok and t1_regression_ok)
    return {
        "metric": "cascade_escalated_frac",
        "value": (None if escalated_frac is None
                  else round(escalated_frac, 4)),
        "unit": "frac",
        "backend": backend,
        "device_kind": device_kind,
        "band": [round(float(band[0]), 6), round(float(band[1]), 6)],
        "expected_frac": (None if expected_frac is None
                          else round(float(expected_frac), 4)),
        "escalated_frac": (None if escalated_frac is None
                           else round(escalated_frac, 4)),
        "escalation_tol": CASCADE_ESCALATION_TOL,
        "escalation_ok": escalation_ok,
        "escalated_total": int(escalated_total),
        "answered_tier2": int(answered_tier2),
        "degraded_total": int(degraded_total),
        "requests_total": int(requests_total),
        "tier1_p50_ms": (None if tier1_p50_ms is None
                         else round(float(tier1_p50_ms), 3)),
        "baseline_p50_ms": (None if baseline_p50_ms is None
                            else round(float(baseline_p50_ms), 3)),
        "max_t1_p50_regression": CASCADE_MAX_T1_P50_REGRESSION,
        "t1_regression_ok": t1_regression_ok,
        "tier2_p50_ms": (None if tier2_p50_ms is None
                         else round(float(tier2_p50_ms), 3)),
        "tier2_p99_ms": (None if tier2_p99_ms is None
                         else round(float(tier2_p99_ms), 3)),
        "errors_total": int(errors_total),
        "notes": notes or {},
        "ok": ok,
        **_provenance_fields(),
    }


def overlap_fraction(encode_intervals, dispatch_intervals):
    """Fraction of total encode-active time that overlapped at least one
    engine dispatch. Pure interval math over ``(start, end)`` pairs that
    share one clock: union each side, sweep the intersections, divide by
    the encode union's length. None when nothing was encoded — the gate
    (``> 0``) treats that as a failure, not a free pass."""
    def _union(intervals):
        merged: list[list[float]] = []
        for s, e in sorted((float(s), float(e)) for s, e in intervals):
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return merged

    enc, dis = _union(encode_intervals), _union(dispatch_intervals)
    total = sum(e - s for s, e in enc)
    if total <= 0:
        return None
    shared, i, j = 0.0, 0, 0
    while i < len(enc) and j < len(dis):
        lo = max(enc[i][0], dis[j][0])
        hi = min(enc[i][1], dis[j][1])
        if hi > lo:
            shared += hi - lo
        if enc[i][1] <= dis[j][1]:
            i += 1
        else:
            j += 1
    return shared / total


# frontend gates: cold-phase pool encode throughput vs the inline baseline
# from the same corpus shape. Like the extraction pool, the >= 0.75x/worker
# scaling claim needs the host to actually have the cores — a 1-CPU box
# records the honest measurement with scaling_ok: null and gates on the
# structural invariants alone: zero errors, a measured encode↔dispatch
# overlap (the pool actually hid frontend work behind device dispatches),
# and a pool-death phase in which every request was still answered via
# inline encode with /healthz green (standing invariant 25).
FRONTEND_MIN_SCALING = 0.75


def assemble_frontend_result(backend, device_kind, mode, n_workers,
                             host_cpus, inline_rps, pool_rps, encode_p50_ms,
                             encode_p99_ms, queue_wait_ms, overlap_frac,
                             requests_total, errors_total,
                             degraded_requests_total, degraded_errors_total,
                             degraded_inline_total, degraded_health_green,
                             notes=None):
    """ONE-line ``frontend`` block for ``bench_serving.py --frontend``.

    ``inline_rps`` / ``pool_rps`` are matched cold-phase (zero cache hits)
    request rates without and with the encode pool; the ``degraded_*``
    fields come from a third phase that kills the pool mid-load and
    requires every remaining request to complete via inline fallback
    (``degraded_inline_total`` > 0 proves the fallback path actually ran,
    ``degraded_health_green`` pins /healthz) with zero errors."""
    scaling = None
    if inline_rps and pool_rps is not None:
        scaling = pool_rps / inline_rps
    scaling_ok = None
    if scaling is not None and host_cpus is not None and host_cpus >= n_workers:
        scaling_ok = scaling >= FRONTEND_MIN_SCALING * n_workers
    overlap_ok = overlap_frac is not None and overlap_frac > 0.0
    degraded_ok = (degraded_requests_total > 0
                   and degraded_errors_total == 0
                   and degraded_inline_total > 0
                   and bool(degraded_health_green))
    ok = (requests_total > 0 and errors_total == 0
          and overlap_ok and degraded_ok and scaling_ok is not False)
    return {
        "metric": "frontend_pool_requests_per_sec",
        "value": None if pool_rps is None else round(float(pool_rps), 2),
        "unit": "req/s",
        "backend": backend,
        "device_kind": device_kind,
        "mode": mode,
        "n_workers": int(n_workers),
        "host_cpus": host_cpus,
        "inline_requests_per_sec": (
            None if inline_rps is None else round(float(inline_rps), 2)),
        "pool_requests_per_sec": (
            None if pool_rps is None else round(float(pool_rps), 2)),
        "scaling_vs_inline": None if scaling is None else round(scaling, 2),
        "min_scaling_per_worker": FRONTEND_MIN_SCALING,
        "scaling_ok": scaling_ok,
        "encode_p50_ms": (
            None if encode_p50_ms is None else round(float(encode_p50_ms), 3)),
        "encode_p99_ms": (
            None if encode_p99_ms is None else round(float(encode_p99_ms), 3)),
        "queue_wait_ms": (
            None if queue_wait_ms is None else round(float(queue_wait_ms), 3)),
        "overlap_frac": (
            None if overlap_frac is None else round(float(overlap_frac), 4)),
        "overlap_ok": overlap_ok,
        "requests_total": int(requests_total),
        "errors_total": int(errors_total),
        "degraded_requests_total": int(degraded_requests_total),
        "degraded_errors_total": int(degraded_errors_total),
        "degraded_inline_total": int(degraded_inline_total),
        "degraded_health_green": bool(degraded_health_green),
        "degraded_ok": degraded_ok,
        "notes": notes or {},
        "ok": ok,
        **_provenance_fields(),
    }


# fleet gate: aggregate COLD throughput of N router-fronted replicas vs the
# single-replica baseline from the same checkpoint. Linear scaling is the
# ideal; 0.75x/replica absorbs router hop + shard imbalance. Like the strict-
# latency anchor this is a DEVICE-PARALLELISM claim, so it is enforced on TPU
# only: an N-replica fleet multiplexed onto one starved CPU core cannot
# exhibit it, and a CPU artifact that "passed" would be a lie. CPU runs
# record speedup_ok: null and gate on the structural invariants alone.
FLEET_MIN_SPEEDUP_FRAC = 0.75


def assemble_fleet_result(backend, device_kind, n_replicas, single_cold_rps,
                          fleet_cold_rps, aggregate_p50_ms, aggregate_p99_ms,
                          per_replica, shard_cache_hits, join_cold_compiles,
                          compile_seconds_saved, load_x, errors_total,
                          notes=None):
    """ONE-line ``fleet`` block for ``bench_serving.py --fleet N``.

    Structural gates (ALWAYS enforced — they are topology claims, not
    speed claims): zero errors under ``load_x``× load; every replica took
    traffic (the ring actually spread the keyspace); the sharded cache
    produced hits (hot keys came back to the replica that cached them);
    the joining replicas warmed from the store with ZERO cold bucket
    compiles and positive journaled compile-seconds-saved. The speedup
    gate (``fleet_cold_rps >= FLEET_MIN_SPEEDUP_FRAC * n_replicas *
    single_cold_rps``, matched cold-phase workloads) applies on TPU;
    elsewhere ``speedup_ok`` is null and the measured speedup is recorded
    honestly."""
    speedup = None
    if single_cold_rps and fleet_cold_rps:
        speedup = round(float(fleet_cold_rps) / float(single_cold_rps), 3)
    min_speedup = round(FLEET_MIN_SPEEDUP_FRAC * n_replicas, 3)
    speedup_ok = None
    if backend == "tpu":
        speedup_ok = speedup is not None and speedup >= min_speedup
    all_routed = bool(per_replica) and all(
        r.get("forwarded", 0) > 0 for r in per_replica.values())
    structural_ok = (n_replicas >= 2 and errors_total == 0
                     and all_routed
                     and shard_cache_hits > 0
                     and join_cold_compiles == 0
                     and compile_seconds_saved is not None
                     and compile_seconds_saved > 0)
    return {
        "metric": "fleet_requests_per_sec",
        "value": (None if fleet_cold_rps is None
                  else round(float(fleet_cold_rps), 2)),
        "unit": "req/s",
        "backend": backend,
        "device_kind": device_kind,
        "n_replicas": int(n_replicas),
        "single_replica_rps": (None if single_cold_rps is None
                               else round(float(single_cold_rps), 2)),
        "speedup_vs_single": speedup,
        "min_speedup": min_speedup,
        "speedup_ok": speedup_ok,
        "aggregate_p50_ms": (None if aggregate_p50_ms is None
                             else round(float(aggregate_p50_ms), 3)),
        "aggregate_p99_ms": (None if aggregate_p99_ms is None
                             else round(float(aggregate_p99_ms), 3)),
        "per_replica": per_replica,
        "all_replicas_routed": all_routed,
        "shard_cache_hits": int(shard_cache_hits),
        "join_cold_compiles": int(join_cold_compiles),
        "compile_seconds_saved": (
            None if compile_seconds_saved is None
            else round(float(compile_seconds_saved), 3)),
        "load_x": load_x,
        "errors_total": int(errors_total),
        "notes": notes or {},
        "ok": structural_ok and speedup_ok is not False,
        **_provenance_fields(),
    }


# autoscale gate: minutes of SLO-alert time the sawtooth is allowed to burn
# while the fleet resizes and a killed replica is replaced. The swing is 10x
# and the kill lands mid-load, so SOME burn is expected — the budget bounds
# how long the fleet may page before capacity catches up.
AUTOSCALE_MAX_BURN_MINUTES = 1.0


def assemble_autoscale_result(backend, device_kind, min_replicas,
                              max_replicas, replace_deadline_s, summary,
                              slo_burn_minutes, errors_total, notes=None):
    """ONE-line ``autoscale`` block for ``bench_serving.py --autoscale``.

    ``summary`` is :meth:`Autoscaler.summary` — every decision the loop
    made, verbatim, so the artifact is the audit trail. The gates are the
    chaos acceptance criteria: the ``kill -9``'d replica was replaced
    within ``replace_deadline_s`` and its replacement warm-joined with
    ZERO cold compiles (invariant 11); the loop scaled up under the 10x
    swing without a single spawn give-up; SLO burn stayed within the
    bench budget; and zero request errors surfaced beyond the failover
    window (the ring absorbed the crash)."""
    decisions = summary.get("decisions") or []
    replacements = int(summary.get("replacements") or 0)
    replace_latency_s = summary.get("replace_latency_s")
    join_cold_compiles = summary.get("join_cold_compiles")
    spawn_give_ups = int(summary.get("spawn_give_ups") or 0)
    scale_ups = sum(d.get("action") == "scale_up" for d in decisions)
    scale_downs = sum(d.get("action") == "scale_down" for d in decisions)
    replaced_in_time = (replacements > 0
                        and replace_latency_s is not None
                        and replace_latency_s <= replace_deadline_s)
    ok = (replaced_in_time
          and join_cold_compiles == 0
          and spawn_give_ups == 0
          and scale_ups > 0
          and errors_total == 0
          and len(decisions) == int(summary.get("scale_decisions") or 0)
          and slo_burn_minutes is not None
          and slo_burn_minutes <= AUTOSCALE_MAX_BURN_MINUTES)
    return {
        "metric": "autoscale_replace_latency_s",
        "value": (None if replace_latency_s is None
                  else round(float(replace_latency_s), 3)),
        "unit": "s",
        "backend": backend,
        "device_kind": device_kind,
        "min_replicas": int(min_replicas),
        "max_replicas": int(max_replicas),
        "replace_deadline_s": round(float(replace_deadline_s), 3),
        "replace_latency_s": (None if replace_latency_s is None
                              else round(float(replace_latency_s), 3)),
        "replaced_in_time": replaced_in_time,
        "slo_burn_minutes": (None if slo_burn_minutes is None
                             else round(float(slo_burn_minutes), 3)),
        "max_burn_minutes": AUTOSCALE_MAX_BURN_MINUTES,
        "scale_decisions": len(decisions),
        "scale_ups": int(scale_ups),
        "scale_downs": int(scale_downs),
        "replacements": replacements,
        "join_cold_compiles": (None if join_cold_compiles is None
                               else int(join_cold_compiles)),
        "spawn_give_ups": spawn_give_ups,
        "errors_total": int(errors_total),
        "decisions": decisions,
        "notes": notes or {},
        "ok": ok,
        **_provenance_fields(),
    }


# admission gates (scripts/bench_serving.py --overload): the sawtooth
# saturates the fleet at ADMISSION_SATURATION_X times the nominal rate, so
# the explicit overload behavior (ISSUE 18, invariant candidate 30) is
# what is measured — sheds ARE expected, what is gated is their shape:
# every shed a 429 with a Retry-After header, zero 5xx anywhere (the
# interactive class above all), the batch class shed first, interactive
# shed only after the brownout ladder reached its last level, nominal
# load shedding NOTHING, and the SLO burn the sawtooth pages bounded by
# the brownout budget.
ADMISSION_SATURATION_X = 10
ADMISSION_MAX_BURN_MINUTES = 2.0
ADMISSION_MAX_NOMINAL_SHEDS = 0


def assemble_admission_result(backend, device_kind, saturation_x, nominal,
                              overload, admission, brownout,
                              slo_burn_minutes, healthz_brownout_level_max,
                              notes=None):
    """ONE-line ``admission`` block for ``bench_serving.py --overload``.

    ``nominal``/``overload`` are per-phase collector dicts (requests,
    per-class response codes, Retry-After header presence on 429s);
    ``admission``/``brownout`` are the controllers' own summaries — the
    artifact doubles as the audit trail, exactly like the autoscale
    block. The gates are the ISSUE 18 acceptance criteria verbatim."""
    def _code_total(phase, pred, klass=None):
        total = 0
        for cls, codes in (phase.get("responses") or {}).items():
            if klass is not None and cls != klass:
                continue
            total += sum(n for code, n in codes.items() if pred(int(code)))
        return total

    nominal_sheds = _code_total(nominal, lambda c: c == 429)
    overload_sheds = _code_total(overload, lambda c: c == 429)
    batch_sheds = _code_total(overload, lambda c: c == 429, klass="batch")
    interactive_5xx = (_code_total(nominal, lambda c: c >= 500,
                                   klass="interactive")
                       + _code_total(overload, lambda c: c >= 500,
                                     klass="interactive"))
    total_5xx = (_code_total(nominal, lambda c: c >= 500)
                 + _code_total(overload, lambda c: c >= 500))
    retry_after_missing = (int(nominal.get("retry_after_missing") or 0)
                           + int(overload.get("retry_after_missing") or 0))
    early_interactive = int(
        admission.get("interactive_sheds_before_brownout") or 0)
    journal_drops = (int(admission.get("journal_drops") or 0)
                     + int(brownout.get("journal_drops") or 0))
    brownout_escalated = int(brownout.get("transitions_total") or 0) > 0
    # /healthz must have reported the degradation while it was happening
    healthz_honest = (not brownout_escalated
                      or (healthz_brownout_level_max or 0) >= 1)
    ok = (int(nominal.get("requests_total") or 0) > 0
          and int(overload.get("requests_total") or 0) > 0
          and nominal_sheds <= ADMISSION_MAX_NOMINAL_SHEDS
          and overload_sheds > 0         # the saturation actually shed
          and batch_sheds > 0            # ... starting with the batch class
          and total_5xx == 0
          and interactive_5xx == 0
          and retry_after_missing == 0
          and early_interactive == 0     # interactive sheds LAST
          and journal_drops == 0
          and brownout_escalated
          and healthz_honest
          and slo_burn_minutes is not None
          and slo_burn_minutes <= ADMISSION_MAX_BURN_MINUTES)
    return {
        "metric": "admission_slo_burn_minutes",
        "value": (None if slo_burn_minutes is None
                  else round(float(slo_burn_minutes), 3)),
        "unit": "min",
        "backend": backend,
        "device_kind": device_kind,
        "saturation_x": int(saturation_x),
        "nominal_shed_total": int(nominal_sheds),
        "max_nominal_sheds": ADMISSION_MAX_NOMINAL_SHEDS,
        "overload_shed_total": int(overload_sheds),
        "batch_shed_total": int(batch_sheds),
        "interactive_5xx_total": int(interactive_5xx),
        "responses_5xx_total": int(total_5xx),
        "retry_after_missing": int(retry_after_missing),
        "interactive_sheds_before_brownout": early_interactive,
        "journal_drops": int(journal_drops),
        "slo_burn_minutes": (None if slo_burn_minutes is None
                             else round(float(slo_burn_minutes), 3)),
        "max_burn_minutes": ADMISSION_MAX_BURN_MINUTES,
        "brownout_transitions": int(brownout.get("transitions_total") or 0),
        "brownout_max_level": int(brownout.get("max_level_seen") or 0),
        "healthz_brownout_level_max": (
            None if healthz_brownout_level_max is None
            else int(healthz_brownout_level_max)),
        "healthz_honest": healthz_honest,
        "nominal": nominal,
        "overload": overload,
        "admission_summary": admission,
        "brownout_summary": brownout,
        "notes": notes or {},
        "ok": ok,
        **_provenance_fields(),
    }


# -- dispatch-gap stages (fused train / int8 serving / strict latency) -------

# VMEM-sized TRAIN batches: the fused training kernel banks n_steps node-state
# blocks plus gate temps on top of the forward working set (~2x), so the
# fused-train stage halves the forward stage's 128-graph bucket again —
# bench_fused_train walks further down if the corpus-derived shape still
# exceeds fits_vmem_train.
FUSED_TRAIN_BATCH_GRAPHS = 64
FUSED_TRAIN_MAX_RATIO = 0.8      # gate: fused train step_ms <= 0.8x segment
STRICT_LATENCY_MAX_RATIO = 0.25  # gate: latency-mode step_ms <= 0.25x strict
R05_STRICT_STEP_MS = 71.0        # the r05 strict-dispatch anchor (TPU)
R05_CHAINED_MFU = 0.0358         # r05 chained headline: 3.6% of the roofline
MEGABATCH_MFU_TARGET_RATIO = 2.0  # gate: megabatch MFU >= 2x the r05 anchor
MEGABATCH_EFFICIENCY_FLOOR = 0.95  # graphs-axis packing efficiency target
LATENCY_WINDOW_DEPTH = 8         # in-flight submits in the latency-mode loop


def assemble_fused_train_result(backend, device_kind, fused, segment,
                                batch_graphs, error=None):
    """ONE-line block for the ``ggnn_fused_train`` stage: fused-layout train
    step (Pallas fwd + fused recompute-backward inside one jitted dispatch)
    vs the segment twin on the SAME batches. ``ok`` encodes the acceptance
    gate: fused ``step_ms`` at or under ``FUSED_TRAIN_MAX_RATIO`` of the
    segment step."""
    ratio = None
    if fused and segment and segment.get("step_ms"):
        ratio = fused["step_ms"] / segment["step_ms"]
    ok = (error is None and ratio is not None
          and ratio <= FUSED_TRAIN_MAX_RATIO)
    return {
        "metric": "ggnn_fused_train_step_ms",
        "value": round(fused["step_ms"], 3) if fused else None,
        "unit": "ms/step",
        "backend": backend,
        "device_kind": device_kind,
        "segment_step_ms": round(segment["step_ms"], 3) if segment else None,
        "fused_graphs_per_sec": (
            round(fused["graphs_per_sec"], 1) if fused else None),
        "segment_graphs_per_sec": (
            round(segment["graphs_per_sec"], 1) if segment else None),
        "ratio_vs_segment": None if ratio is None else round(ratio, 4),
        "max_ratio": FUSED_TRAIN_MAX_RATIO,
        "batch_graphs": batch_graphs,
        "config": GOLDEN_CONFIG,
        "error": error,
        "ok": ok,
        **_provenance_fields(),
    }


def assemble_strict_latency_result(backend, device_kind, strict_step_ms,
                                   latency_step_ms, window, requests,
                                   error=None):
    """ONE-line block for the ``strict_latency`` stage: per-request latency
    of the warm donated-buffer engine loop (``ScoringEngine.submit`` with
    ``window`` results in flight) vs the strict score-and-sync path,
    measured in the SAME run. ``ok`` gates the ratio at
    ``STRICT_LATENCY_MAX_RATIO``; on TPU the r05 71 ms strict anchor is
    ALSO enforced (that is the dispatch gap this stage exists to close —
    off-TPU the anchor is recorded but not comparable)."""
    ratio = None
    if strict_step_ms and latency_step_ms is not None:
        ratio = latency_step_ms / strict_step_ms
    anchor_ok = None
    if backend == "tpu" and latency_step_ms is not None:
        anchor_ok = (latency_step_ms
                     <= STRICT_LATENCY_MAX_RATIO * R05_STRICT_STEP_MS)
    ok = (error is None and ratio is not None
          and ratio <= STRICT_LATENCY_MAX_RATIO
          and anchor_ok is not False)
    return {
        "metric": "strict_latency_step_ms",
        "value": None if latency_step_ms is None else round(latency_step_ms, 3),
        "unit": "ms/request",
        "backend": backend,
        "device_kind": device_kind,
        "strict_step_ms": (
            None if strict_step_ms is None else round(strict_step_ms, 3)),
        "ratio_vs_strict": None if ratio is None else round(ratio, 4),
        "max_ratio": STRICT_LATENCY_MAX_RATIO,
        "anchor_strict_step_ms": R05_STRICT_STEP_MS,
        "anchor_ok": anchor_ok,
        "window": window,
        "requests": requests,
        "error": error,
        "ok": ok,
        **_provenance_fields(),
    }


def assemble_int8_serving_result(backend, device_kind, precision_served,
                                 int8_score_delta, max_score_delta, tiers,
                                 refused_reason=None, error=None):
    """ONE-line block for the ``int8_serving`` stage: tier-level p50/p99
    for both precisions plus the calibration gate verdict. ``ok`` means the
    gate was RESPECTED — either int8 was served with its measured score
    delta within ``max_score_delta``, or it was refused and the engine fell
    back to f32 with a recorded reason (the refusal path working is a pass,
    not a failure)."""
    gate_respected = (
        (precision_served == "int8" and int8_score_delta is not None
         and int8_score_delta <= max_score_delta)
        or (precision_served == "f32" and refused_reason is not None))
    ok = error is None and gate_respected
    return {
        "metric": "int8_serving_precision",
        "value": precision_served,
        "unit": "precision",
        "backend": backend,
        "device_kind": device_kind,
        "int8_score_delta": (
            None if int8_score_delta is None
            else round(float(int8_score_delta), 6)),
        "max_score_delta": max_score_delta,
        "refused_reason": refused_reason,
        # {graph_nodes: {"f32": {p50_ms, p99_ms}, "int8": {...}|None}}
        "tiers": tiers,
        "error": error,
        "ok": ok,
        **_provenance_fields(),
    }


EXTRACTION_MIN_SCALING = 0.75  # gate: pool fns/sec >= 0.75*N x serial, N workers


def assemble_extraction_result(n_functions, n_workers, host_cpus,
                               serial_fps, pool_fps, warm_hit_rate,
                               warm_extracted, n_results, quarantined,
                               steals=0, error=None):
    """ONE-line block for the ``extraction`` stage
    (``scripts/bench_extraction.py --pool``): cold pool throughput vs the
    serial baseline, then a warm re-scan of the SAME corpus against the
    populated cache. Structural gates that always apply: every item came
    back exactly once (``n_results == n_functions``) and the warm re-scan
    performed ZERO extractions (``cache_hit_rate == 1.0``). The
    ``>= EXTRACTION_MIN_SCALING x N`` scaling gate is enforced only when
    the host actually has N cores — on a 1-2 core box thread fan-out
    cannot scale and the honest measurement is recorded ungated (the
    strict-latency TPU-anchor pattern)."""
    scaling = None
    if serial_fps and pool_fps is not None:
        scaling = pool_fps / serial_fps
    scaling_ok = None
    if scaling is not None and host_cpus is not None and host_cpus >= n_workers:
        scaling_ok = scaling >= EXTRACTION_MIN_SCALING * n_workers
    warm_ok = (warm_hit_rate is not None and warm_hit_rate >= 1.0
               and warm_extracted == 0)
    ok = (error is None and n_results == n_functions and warm_ok
          and scaling_ok is not False)
    return {
        "metric": "extraction_pool_functions_per_sec",
        "value": None if pool_fps is None else round(pool_fps, 1),
        "unit": "functions/sec",
        "backend": "cpu",
        "device_kind": "host",
        "extraction": {
            "functions_per_sec": (
                None if pool_fps is None else round(pool_fps, 1)),
            "cache_hit_rate": (
                None if warm_hit_rate is None else round(warm_hit_rate, 4)),
            "quarantined": quarantined,
        },
        "n_functions": n_functions,
        "n_results": n_results,
        "n_workers": n_workers,
        "host_cpus": host_cpus,
        "serial_functions_per_sec": (
            None if serial_fps is None else round(serial_fps, 1)),
        "scaling_vs_serial": None if scaling is None else round(scaling, 2),
        "min_scaling_per_worker": EXTRACTION_MIN_SCALING,
        "scaling_ok": scaling_ok,
        "warm_extracted": warm_extracted,
        "steals": steals,
        "error": error,
        "ok": ok,
        **_provenance_fields(),
    }


def assemble_interproc_result(n_functions, n_call_edges, supergraph_build_ms,
                              solve_ms, functions_per_sec, parity_ok,
                              n_cross_findings, error=None):
    """ONE-line block for the ``interproc`` stage
    (``scripts/bench_extraction.py --interproc``): supergraph construction
    cost plus the interprocedural taint solve per backend over a seeded
    multi-function corpus. ``solve_ms`` maps backend name → milliseconds
    and is flattened to ``solve_<backend>_ms`` keys so the ledger walker
    picks each up as its own series. Gates: the zero-call-edge parity
    property held during the run (``parity_ok`` — correctness is a
    precondition of any perf number), and the seeded cross-function flows
    were actually found (``n_cross_findings >= 1`` — a solver that is fast
    because it found nothing is not a result)."""
    ok = (error is None and parity_ok is True and n_cross_findings >= 1
          and all(v is not None for v in solve_ms.values()))
    return {
        "metric": "interproc_supergraph_build_ms",
        "value": (None if supergraph_build_ms is None
                  else round(supergraph_build_ms, 3)),
        "unit": "ms",
        "backend": "cpu",
        "device_kind": "host",
        "interproc": {
            "supergraph_build_ms": (
                None if supergraph_build_ms is None
                else round(supergraph_build_ms, 3)),
            **{f"solve_{name}_ms": (None if ms is None else round(ms, 3))
               for name, ms in sorted(solve_ms.items())},
            "functions_per_sec": (
                None if functions_per_sec is None
                else round(functions_per_sec, 1)),
        },
        "n_functions": n_functions,
        "n_call_edges": n_call_edges,
        "n_cross_findings": n_cross_findings,
        "parity_ok": parity_ok,
        "error": error,
        "ok": ok,
        **_provenance_fields(),
    }


def assemble_hier_result(n_functions, n_call_edges, cold_unit_score_ms,
                         warm_unit_score_ms, embed_cache_hit_rate,
                         level1_recompute, fallback_dispatches,
                         level1_dispatches_cold, unit_score, error=None):
    """ONE-line block for the ``hier`` stage (``scripts/bench_hier.py``):
    whole-unit hierarchical scoring over a seeded multi-function corpus,
    cold (empty embedding cache) then warm (same content re-scored).
    Warm-pass numbers are the headline: ``unit_score_ms`` is the warm
    latency, ``level1_recompute`` the warm-pass function re-embeds and
    ``embed_cache_hit_rate`` the warm-pass cache hit fraction. Gates:
    (a) ``fallback_dispatches == 0`` across BOTH passes — the whole point
    of the hierarchical path is that whole-program scoring never leaves
    the fused megabatch kernels; (b) ``level1_recompute == 0`` warm — a
    content-addressed cache that re-embeds unchanged functions is not a
    cache; (c) the warm hit rate covers every function; (d) warm at least
    broke even (``warm_speedup >= 1``); (e) the unit score survived both
    passes bit-identically (checked by the caller, passed as a finite
    ``unit_score`` — None means the scores diverged or scoring failed)."""
    speedup = (None if not warm_unit_score_ms or cold_unit_score_ms is None
               else cold_unit_score_ms / warm_unit_score_ms)
    ok = (error is None and unit_score is not None
          and fallback_dispatches == 0 and level1_recompute == 0
          and level1_dispatches_cold >= 1
          and embed_cache_hit_rate is not None
          and embed_cache_hit_rate >= 1.0
          and speedup is not None and speedup >= 1.0)
    return {
        "metric": "hier_unit_score_ms",
        "value": (None if warm_unit_score_ms is None
                  else round(warm_unit_score_ms, 3)),
        "unit": "ms",
        "backend": "cpu",
        "device_kind": "host",
        "hier": {
            "unit_score_ms": (None if warm_unit_score_ms is None
                              else round(warm_unit_score_ms, 3)),
            "unit_score_cold_ms": (None if cold_unit_score_ms is None
                                   else round(cold_unit_score_ms, 3)),
            "embed_cache_hit_rate": (
                None if embed_cache_hit_rate is None
                else round(embed_cache_hit_rate, 3)),
            "level1_recompute": level1_recompute,
            "fallback_dispatches": fallback_dispatches,
            "warm_speedup": None if speedup is None else round(speedup, 2),
        },
        "n_functions": n_functions,
        "n_call_edges": n_call_edges,
        "level1_dispatches_cold": level1_dispatches_cold,
        "unit_score": unit_score,
        "error": error,
        "ok": ok,
        **_provenance_fields(),
    }


def assemble_promotion_result(n_replicas, capture, shadow_same, shadow_diff,
                              roll, rollback, responses_5xx,
                              prior_rev_restored, notes=None, error=None):
    """ONE-line artifact for the ``promotion`` stage
    (``scripts/bench_promotion.py``): the whole continuous-learning
    sawtooth on a live fleet — capture journaled traffic, shadow-replay
    it against baseline + candidate engines, roll the candidate through
    the router's drain/warm-join protocol, then force the drift watch
    and prove the rollback restores the prior ``model_rev``. Gates are
    the ISSUE 19 acceptance criteria verbatim: (a) the shadow harness is
    honest — identical revs produce a ZERO-diff report while the
    distinct-rev report measures a real difference; (b) the forward roll
    completed with ``join_cold_compiles == 0`` (invariant 11) and zero
    5xx surfaced through the router while replicas were swapped
    (invariants 12/22); (c) the forced-drift leg rolled back —
    ``rollback_total >= 1`` — and the PRIOR rev is what the ring serves
    afterwards (invariant candidate 31's restore half); (d) capture
    dropped nothing (invariant 20 is a counter, not a hope)."""
    shadow_honest = (bool((shadow_same or {}).get("zero_diff"))
                     and (shadow_diff or {}).get("max_abs_delta") is not None
                     and (shadow_diff or {}).get("max_abs_delta", 0) > 0)
    rollout_seconds = (roll or {}).get("rollout_seconds")
    join_cold = ((roll or {}).get("join_cold_compiles", 0)
                 + (rollback or {}).get("join_cold_compiles", 0))
    rollback_total = (rollback or {}).get("rollback_total", 0)
    capture_dropped = int((capture or {}).get("dropped") or 0)
    ok = (error is None
          and shadow_honest
          and bool((roll or {}).get("completed"))
          and rollout_seconds is not None
          and join_cold == 0
          and int(responses_5xx or 0) == 0
          and rollback_total >= 1
          and bool(prior_rev_restored)
          and capture_dropped == 0
          and int((capture or {}).get("written") or 0) > 0)
    return {
        "metric": "promotion_rollout_seconds",
        "value": (None if rollout_seconds is None
                  else round(float(rollout_seconds), 3)),
        "unit": "s",
        "backend": "cpu",
        "device_kind": "host",
        "promotion": {
            "rollout_seconds": (None if rollout_seconds is None
                                else round(float(rollout_seconds), 3)),
            "rollback_total": int(rollback_total),
            "join_cold_compiles": int(join_cold),
        },
        "n_replicas": int(n_replicas),
        "capture": capture or {},
        "shadow_same_max_abs_delta": (shadow_same or {}).get("max_abs_delta"),
        "shadow_same_zero_diff": bool((shadow_same or {}).get("zero_diff")),
        "shadow_diff_max_psi": (shadow_diff or {}).get("max_psi"),
        "shadow_diff_max_abs_delta": (
            shadow_diff or {}).get("max_abs_delta"),
        "responses_5xx_total": int(responses_5xx or 0),
        "prior_rev_restored": bool(prior_rev_restored),
        "roll_completed": bool((roll or {}).get("completed")),
        "notes": notes or {},
        "error": error,
        "ok": ok,
        **_provenance_fields(),
    }


# federation gates (scripts/bench_serving.py --federation N): the
# cell-killed sawtooth SIGKILLs one whole cell under 10x load and gates
# invariant candidate 32 — losing any single cell loses no request: zero
# client-visible 5xx across every phase, the spillover actually served
# off the survivors, every shed carrying its
# Retry-After, the killed cell healed and warm-rejoined (zero cold
# compiles) inside the recovery deadline, and a promotion attempted
# during the brownout refused/paused until recovery, then completed.
FEDERATION_RECOVERY_DEADLINE_S = 60.0


def assemble_federation_result(backend, device_kind, n_cells, nominal,
                               killed, recovery, federation,
                               cell_kill_recovery_s, rejoined,
                               join_cold_compiles,
                               promotion_refused_during_brownout,
                               promotion_completed_after,
                               notes=None, error=None):
    """ONE-line ``federation`` block for ``bench_serving.py
    --federation N``. ``nominal``/``killed``/``recovery`` are per-phase
    collector dicts (requests, response-code histogram, Retry-After
    presence on 429s); ``federation`` is the FederationRouter's own
    metrics snapshot — the artifact doubles as the audit trail, exactly
    like the admission block. The gates are the ISSUE 20 acceptance
    criteria verbatim."""
    def _codes(phase, pred):
        return sum(n for code, n in (phase or {}).get("codes", {}).items()
                   if pred(int(code)))

    phases = [p for p in (nominal, killed, recovery) if p]
    total_5xx = sum(_codes(p, lambda c: c >= 500) for p in phases)
    fleetwide_5xx = max(total_5xx,
                        int((federation or {}).get("fleetwide_5xx_total")
                            or 0))
    retry_after_missing = sum(int(p.get("retry_after_missing") or 0)
                              for p in phases)
    spillover_served = int((federation or {}).get("spillover_total") or 0)
    spillover_errors = int((federation or {}).get("spillover_errors_total")
                           or 0)
    ok = (error is None
          and int((nominal or {}).get("requests_total") or 0) > 0
          and int((killed or {}).get("requests_total") or 0) > 0
          and fleetwide_5xx == 0
          and spillover_served > 0       # survivors actually absorbed it
          # spillover_errors is deliberately NOT a hard gate: a spilled
          # forward racing a dying cell is expected — what matters is the
          # retry served it (zero 5xx above). The ledger tracks the count
          # as a lower-is-better series instead.
          and retry_after_missing == 0
          and cell_kill_recovery_s is not None
          and cell_kill_recovery_s <= FEDERATION_RECOVERY_DEADLINE_S
          and bool(rejoined)
          and int(join_cold_compiles or 0) == 0
          and bool(promotion_refused_during_brownout)
          and bool(promotion_completed_after))
    return {
        "metric": "federation_cell_kill_recovery_s",
        "value": (None if cell_kill_recovery_s is None
                  else round(float(cell_kill_recovery_s), 3)),
        "unit": "s",
        "backend": backend,
        "device_kind": device_kind,
        "n_cells": int(n_cells),
        # the three ledger series (EXPLICIT_SERIES stage "federation") —
        # top-level in this block so the serve artifact's nested
        # "federation" key becomes their stage, the admission-block shape
        "cell_kill_recovery_s": (
            None if cell_kill_recovery_s is None
            else round(float(cell_kill_recovery_s), 3)),
        "spillover_errors": spillover_errors,
        "fleetwide_5xx": fleetwide_5xx,
        "recovery_deadline_s": FEDERATION_RECOVERY_DEADLINE_S,
        "spillover_served": spillover_served,
        "retry_after_missing": int(retry_after_missing),
        "rejoined": bool(rejoined),
        "join_cold_compiles": int(join_cold_compiles or 0),
        "promotion_refused_during_brownout": bool(
            promotion_refused_during_brownout),
        "promotion_completed_after": bool(promotion_completed_after),
        "nominal": nominal or {},
        "killed": killed or {},
        "recovery": recovery or {},
        "federation_metrics": federation or {},
        "notes": notes or {},
        "error": error,
        "ok": ok,
        **_provenance_fields(),
    }


def bench_fused_train(corpus, n_batches: int, k: int,
                      dtype: str = "bfloat16", trials: int = 3):
    """The ``ggnn_fused_train`` stage: chained TRAIN steps (fwd + backward +
    optimizer update per step inside one jitted scan body) through the fused
    layout — whose backward auto-selects the Pallas training kernel on
    fits_vmem_train buckets — vs the segment twin on identical batches.
    Returns ``(fused_run, segment_run, batch_graphs)``."""
    from deepdfa_tpu.config import GGNNConfig
    from deepdfa_tpu.ops.fused_ggnn import fits_vmem_train

    cfg = GGNNConfig()
    width = cfg.out_dim // 2
    bg = FUSED_TRAIN_BATCH_GRAPHS
    while bg >= 8:
        batches, _occ = build_batches(corpus, n_batches, batch_graphs=bg)
        fb = batches[0]
        if fits_vmem_train(fb.max_nodes, fb.senders.shape[0], width,
                           cfg.n_steps):
            break
        bg //= 2
    else:
        raise RuntimeError(
            "no fused-train bucket fits the VMEM training plan — even "
            "8-graph batches exceed fits_vmem_train")
    fused = bench_chained(batches, k, train=True, dtype=dtype, trials=trials,
                          layout="fused")
    segment = bench_chained(batches, k, train=True, dtype=dtype,
                            trials=trials, layout="segment")
    return fused, segment, bg


def _megabatch_flops_per_step(plan) -> float:
    """Kernel-math FLOPs of ONE whole-model launch at the plan's PADDED
    shapes. XLA's cost analysis cannot see inside a Pallas custom call, so
    the megabatch stage counts the matmul work the kernel actually issues:
    ``n_steps`` message rounds (edge projection + both fused 3-gate GRU
    projections), the pooling gate, the one-hot softmax/readout matmuls,
    and the classifier head."""
    from deepdfa_tpu.ops.fused_ggnn import _round_up

    np_ = _round_up(max(plan.max_nodes, 8), 8)
    dp = _round_up(max(plan.width, 1), 128)
    gp = _round_up(max(plan.max_graphs, 1), 128)
    rounds = plan.n_steps * (2 * np_ * dp * dp + 2 * 2 * np_ * dp * 3 * dp)
    gate = 2 * np_ * 2 * dp * 128
    # softmax max/denominator gathers + the [np, gp] x [gp, 2dp] readout
    pool = 3 * 2 * np_ * gp + 2 * np_ * gp * 2 * dp
    layers = max(plan.n_head_layers, 1)
    head = ((layers - 1) * 2 * gp * 2 * dp * 2 * dp
            + 2 * gp * 2 * dp * 128)
    return float(rounds + gate + pool + head)


def bench_megabatch(corpus, n_graphs: int, k: int, dtype: str = "bfloat16",
                    trials: int = 3, int8_steps: int = 4):
    """The ``ggnn_megabatch`` stage: cross-bucket packed megabatches through
    the whole-model fused layout, chained-protocol timing, plus the frozen-
    int8-conv training experiment on the SAME packed batches.

    Returns ``(run, pack, ladder_dispatches, int8_train)`` where ``run`` is
    the chained measurement (graphs/sec over REAL graphs, analytic kernel
    FLOPs), ``pack`` the :class:`~deepdfa_tpu.ops.megabatch.PackResult`
    (uniform-shape mode, so the scan chain compiles once), and
    ``ladder_dispatches`` the number of batches the per-bucket
    ``GraphBatcher`` ladder would dispatch for the same graphs at the
    largest bucket budget the whole-model VMEM plan admits — the
    ``bench_fused_train`` sizing idiom. Comparing against an unadmitted
    bucket would let the ladder "win" with batches only the slow segment
    path could actually launch."""
    from deepdfa_tpu.config import ALL_SUBKEYS, ExperimentConfig
    from deepdfa_tpu.data.graphs import GraphBatcher, derive_buckets
    from deepdfa_tpu.ops.megabatch import (
        fits_vmem_megabatch,
        pack_megabatches,
    )
    from deepdfa_tpu.train.int8_train import run_int8_train

    cfg = ExperimentConfig()
    mcfg = cfg.model
    graphs = list(corpus[:n_graphs])
    bg = cfg.data.batch.batch_graphs
    while bg >= 8:
        buckets = derive_buckets(graphs, bg)
        big = buckets[-1]
        if fits_vmem_megabatch(
                big.max_nodes, big.max_edges,
                mcfg.hidden_dim * len(ALL_SUBKEYS), big.max_graphs,
                table_rows=cfg.input_dim * len(ALL_SUBKEYS),
                embed_width=mcfg.hidden_dim,
                n_head_layers=mcfg.num_output_layers):
            break
        bg //= 2
    else:
        raise RuntimeError(
            "no per-bucket ladder budget fits the whole-model VMEM plan — "
            "even 8-graph buckets exceed fits_vmem_megabatch")
    ladder_dispatches = len(list(GraphBatcher(buckets).batches(graphs)))
    pack = pack_megabatches(
        graphs,
        width=mcfg.hidden_dim * len(ALL_SUBKEYS),
        n_steps=mcfg.n_steps,
        table_rows=cfg.input_dim * len(ALL_SUBKEYS),
        embed_width=mcfg.hidden_dim,
        n_head_layers=mcfg.num_output_layers,
        max_batch_graphs=cfg.data.batch.batch_graphs,
        uniform=True,
    )
    if not pack.batches:
        raise RuntimeError(
            f"packer produced no megabatches from {len(graphs)} graphs "
            f"({len(pack.oversize)} oversize)")
    run = bench_chained(pack.batches, k, train=False, dtype=dtype,
                        trials=trials, layout="megabatch")
    run["flops_per_step"] = _megabatch_flops_per_step(pack.plans[0])
    int8_train = run_int8_train(pack.batches[:2], cfg=cfg,
                                steps=int8_steps)
    return run, pack, ladder_dispatches, int8_train


def assemble_megabatch_result(backend, device_kind, run, pack,
                              ladder_dispatches, roofline, nominal_tflops,
                              int8_train=None, error=None):
    """ONE-line block for the ``ggnn_megabatch`` stage.

    The acceptance contract: on-device the chained MFU must reach
    ``MEGABATCH_MFU_TARGET_RATIO`` × the r05 chained anchor (0.0358) OR
    ``ceiling`` must record exactly which limit was hit — ``vmem_plan_
    refusal`` (the uniform packed shape exceeded the whole-model VMEM
    plan), ``packer_efficiency_floor`` (graphs-axis packing efficiency
    under ``MEGABATCH_EFFICIENCY_FLOOR``), or ``memory_bandwidth_bound``
    (plan fit and packing was efficient, so the hidden-width matmuls'
    arithmetic intensity is the remaining limit). Off-device the gate is
    structural: plan admitted, packing at or above the floor, and
    megabatch dispatches strictly below the per-bucket ladder's.
    FLOPs are kernel-math over the padded shapes (``flops_source``) —
    cost analysis cannot see inside the Pallas call."""
    eff = pack.efficiency if pack is not None else None
    plan = pack.plans[0] if (pack is not None and pack.plans) else None
    dispatches = (len(pack.batches) + len(pack.oversize)
                  if pack is not None else None)
    gps = run["graphs_per_sec"] if run else None
    graphs_per_step = (gps * run["step_ms"] / 1e3
                       if run and run.get("step_ms") else None)
    fpg = (run["flops_per_step"] / graphs_per_step
           if (run and run.get("flops_per_step") and graphs_per_step)
           else None)
    derived = _derived_columns(gps, fpg, roofline / 1e12 if roofline else None,
                               nominal_tflops, None, None)
    mfu = derived["mfu"]
    plan_fits = bool(plan.fits) if plan is not None else None
    dispatch_ok = (dispatches is not None
                   and dispatches < ladder_dispatches
                   if ladder_dispatches else None)
    eff_ok = (eff is not None
              and eff["graphs"] >= MEGABATCH_EFFICIENCY_FLOOR)
    mfu_ok = None
    ceiling = ceiling_note = None
    if error is None and backend == "tpu":
        mfu_ok = (mfu is not None
                  and mfu >= MEGABATCH_MFU_TARGET_RATIO * R05_CHAINED_MFU)
        if plan_fits is False:
            ceiling = "vmem_plan_refusal"
            ceiling_note = (
                f"uniform packed shape needs {plan.working_set} bytes "
                "> the whole-model VMEM plan cap")
        elif not eff_ok:
            ceiling = "packer_efficiency_floor"
            ceiling_note = (
                f"graphs-axis packing efficiency "
                f"{eff['graphs']:.3f} < {MEGABATCH_EFFICIENCY_FLOOR}")
        elif not mfu_ok:
            ceiling = "memory_bandwidth_bound"
            ceiling_note = (
                "plan admitted and packing efficient: the remaining limit "
                "is the conv matmuls' arithmetic intensity (~dp/4 "
                "FLOPs/byte at the padded hidden width, far under the "
                "MXU ridge point)")
    if error is not None:
        ok = False
    elif backend == "tpu":
        ok = bool(dispatch_ok) and (bool(mfu_ok) or ceiling is not None)
    else:
        ok = bool(dispatch_ok) and bool(eff_ok) and plan_fits is True
    return {
        "metric": "ggnn_megabatch_graphs_per_sec",
        "value": round(gps, 1) if gps is not None else None,
        "unit": "graphs/sec",
        "backend": backend,
        "device_kind": device_kind,
        "step_ms": round(run["step_ms"], 3) if run else None,
        "graphs_per_step": (round(graphs_per_step, 1)
                            if graphs_per_step else None),
        "flops_per_step": run.get("flops_per_step") if run else None,
        "flops_source": "kernel-math (padded shapes)",
        "implied_tflops": derived["implied_tflops"],
        "mfu": mfu,
        "mfu_nominal": derived["mfu_nominal"],
        "anchor_chained_mfu": R05_CHAINED_MFU,
        "mfu_target_ratio": MEGABATCH_MFU_TARGET_RATIO,
        "mfu_ok": mfu_ok,
        "packing_efficiency": eff,
        "packing_efficiency_floor": MEGABATCH_EFFICIENCY_FLOOR,
        "dispatches_per_step": dispatches,
        "ladder_dispatches_per_step": ladder_dispatches,
        "oversize_graphs": len(pack.oversize) if pack is not None else None,
        "megabatch_shape": (
            {"max_graphs": plan.max_graphs, "max_nodes": plan.max_nodes,
             "max_edges": plan.max_edges} if plan is not None else None),
        "working_set_bytes": plan.working_set if plan is not None else None,
        "plan_fits": plan_fits,
        "ceiling": ceiling,
        "ceiling_note": ceiling_note,
        "int8_train": int8_train,
        "config": GOLDEN_CONFIG,
        "error": error,
        "ok": ok,
        **_provenance_fields(),
    }


def _serve_engine_fixture(corpus, precision: str = "f32",
                          latency_mode: bool = False,
                          max_score_delta: float = 0.01):
    """Fresh-params live-model engine over the default bucket ladder (the
    serving stages measure DISPATCH, not model accuracy), calibrated/gated
    on corpus graphs when int8 is requested."""
    import warnings as _warnings

    import jax
    import jax.numpy as jnp

    from deepdfa_tpu.config import GGNNConfig
    from deepdfa_tpu.data.graphs import batch_np
    from deepdfa_tpu.models import make_model
    from deepdfa_tpu.serve.engine import ScoringEngine

    cfg = GGNNConfig()
    feat_keys = tuple(sorted(
        k for k in corpus[0].node_feats if not k.startswith("_VULN")))
    from deepdfa_tpu.config import FeatureConfig

    model = make_model(cfg, input_dim=FeatureConfig().input_dim)
    example = jax.tree.map(jnp.asarray, batch_np(corpus[:2], 3, 256, 1024))
    params = model.init(jax.random.key(0), example)["params"]
    refusal = None
    with _warnings.catch_warnings(record=True) as caught:
        _warnings.simplefilter("always")
        engine = ScoringEngine.from_model(
            model, params, cfg.label_style, feat_keys,
            precision=precision, int8_max_score_delta=max_score_delta,
            latency_mode=latency_mode, calibration_graphs=corpus[:32])
        engine.warmup()
    for w in caught:
        if "int8 serving path refused" in str(w.message):
            refusal = str(w.message)
    return engine, refusal


def bench_strict_latency(corpus, requests: int = 64,
                         window: int = LATENCY_WINDOW_DEPTH):
    """The ``strict_latency`` stage: per-request wall time of (a) the strict
    path — ``score()`` with a host sync every request — vs (b) the warm
    latency-mode loop — ``submit()`` keeping ``window`` donated dispatches
    in flight, syncing only the oldest. Single-graph requests on the small
    bucket: per-dispatch overhead IS the quantity under test. Returns
    ``(strict_step_ms, latency_step_ms)``."""
    engine, _ = _serve_engine_fixture(corpus, latency_mode=True)
    gs = [g for g in corpus if engine.buckets[0].admits(g)][:requests]
    if not gs:
        raise RuntimeError("no corpus graph fits the smallest serving bucket")
    bucket = engine.buckets[0]
    reqs = [gs[i % len(gs)] for i in range(requests)]

    # strict: score + host sync per request (what a one-at-a-time caller sees)
    engine.latency_mode = False
    engine.score([reqs[0]], bucket)  # warm (already compiled by warmup)
    t0 = time.perf_counter()
    for g in reqs:
        engine.score([g], bucket)
    strict_ms = (time.perf_counter() - t0) / len(reqs) * 1e3

    # latency mode: window-deep in-flight donated dispatches, one blocking
    # read per request ONCE the pipe is full
    engine.latency_mode = True
    pending = []
    for g in reqs[:window]:
        pending.append(engine.submit([g], bucket))  # fill (untimed)
    t0 = time.perf_counter()
    for g in reqs:
        pending.append(engine.submit([g], bucket))
        pending.pop(0).result()
    latency_ms = (time.perf_counter() - t0) / len(reqs) * 1e3
    for p in pending:
        p.result()
    return strict_ms, latency_ms


def bench_int8_serving(corpus, requests_per_tier: int = 24,
                       max_score_delta: float = 0.01):
    """The ``int8_serving`` stage: per-tier p50/p99 of single-graph
    ``score()`` dispatches at f32 and (gate permitting) int8. Returns the
    kwargs for :func:`assemble_int8_serving_result` minus backend fields."""
    eng_f32, _ = _serve_engine_fixture(corpus)
    eng_int8, refusal = _serve_engine_fixture(
        corpus, precision="int8", max_score_delta=max_score_delta)

    def _tier_lat(engine, bucket):
        gs = [g for g in corpus if bucket.admits(g)][:requests_per_tier]
        if not gs:
            return None
        engine.score([gs[0]], bucket)  # warm
        lat = []
        for i in range(requests_per_tier):
            g = gs[i % len(gs)]
            t0 = time.perf_counter()
            engine.score([g], bucket)
            lat.append((time.perf_counter() - t0) * 1e3)
        return {"p50_ms": round(float(np.percentile(lat, 50)), 3),
                "p99_ms": round(float(np.percentile(lat, 99)), 3)}

    tiers = {}
    for b32, b8 in zip(eng_f32.buckets, eng_int8.buckets):
        tiers[str(b32.graph_nodes)] = {
            "f32": _tier_lat(eng_f32, b32),
            "int8": (_tier_lat(eng_int8, b8)
                     if eng_int8.precision == "int8" else None),
        }
    return {
        "precision_served": eng_int8.precision,
        "int8_score_delta": eng_int8.int8_score_delta,
        "max_score_delta": max_score_delta,
        "tiers": tiers,
        "refused_reason": refusal,
    }


def bench_sentinel_overhead(batches, steps: int = 20, dtype: str = "bfloat16",
                            repeats: int = 3):
    """Median train-step time with the divergence-sentinel guard compiled in
    vs out (``ResilienceConfig.sentinel``) — the guard is a handful of
    ``isfinite`` reductions + a predicated tree-select fused into the update,
    so its cost must stay under the 2% budget."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from deepdfa_tpu.config import ExperimentConfig, ResilienceConfig
    from deepdfa_tpu.models import make_model
    from deepdfa_tpu.train.loop import Trainer
    from deepdfa_tpu.train.metrics import ConfusionState

    dev = [jax.tree.map(jnp.asarray, b) for b in batches]

    def _median_step(sentinel: bool) -> float:
        cfg = ExperimentConfig()
        cfg = dataclasses.replace(
            cfg,
            model=dataclasses.replace(cfg.model, dtype=dtype),
            resilience=ResilienceConfig(sentinel=sentinel),
        )
        model = make_model(cfg.model, input_dim=cfg.input_dim)
        trainer = Trainer(model=model, cfg=cfg, pos_weight=15.0)
        state = trainer.init_state(dev[0])
        step = trainer.train_step
        metrics = ConfusionState.zeros()
        state, metrics, loss, _ = step(state, dev[0], metrics)  # compile
        jax.block_until_ready(loss)
        box = {"state": state, "metrics": metrics, "i": 0}

        def run_once():
            b = dev[box["i"] % len(dev)]
            box["i"] += 1
            box["state"], box["metrics"], loss, _ = step(
                box["state"], b, box["metrics"]
            )
            return loss

        return min(_timed(run_once, steps)[0] for _ in range(repeats))

    plain = _median_step(False)
    guarded = _median_step(True)
    pct = sentinel_overhead_pct(plain, guarded)
    return {
        "plain_step_ms": round(plain * 1e3, 3),
        "guarded_step_ms": round(guarded * 1e3, 3),
        "overhead_pct": round(pct, 2),
        "ok": sentinel_guard_ok(pct),
    }


def bench_emergency_ckpt(batches, repeats: int = 3):
    """Emergency-checkpoint commit latency: a real model state saved through
    ``CheckpointManager.save_emergency`` (the SIGTERM path) must land inside
    the ``ResilienceConfig.preempt_deadline_s`` budget — the whole point of
    the preemption contract is that the grace window is long enough for the
    atomic tmp-dir + os.replace commit. Min of ``repeats`` (best case on a
    loaded host; a cold filesystem outlier must not fail the guard)."""
    import shutil
    import tempfile
    from pathlib import Path

    import jax
    import jax.numpy as jnp

    from deepdfa_tpu.config import ExperimentConfig, ResilienceConfig
    from deepdfa_tpu.models import make_model
    from deepdfa_tpu.train.checkpoint import CheckpointManager
    from deepdfa_tpu.train.loop import Trainer

    deadline_s = ResilienceConfig().preempt_deadline_s
    cfg = ExperimentConfig()
    model = make_model(cfg.model, input_dim=cfg.input_dim)
    trainer = Trainer(model=model, cfg=cfg, pos_weight=15.0)
    state = trainer.init_state(jax.tree.map(jnp.asarray, batches[0]))
    aux = {"opt_state": state.opt_state,
           "rng": jax.random.key_data(state.rng),
           "step": state.step}
    work = tempfile.mkdtemp(prefix="bench_emergency_")
    try:
        commits = []
        for i in range(repeats):
            ckpts = CheckpointManager(Path(work) / f"r{i}", cfg.checkpoint)
            commits.append(ckpts.save_emergency(
                i, {"params": state.params}, epoch=0, aux=aux,
                mesh={"devices": jax.device_count(),
                      "platform": jax.default_backend(), "axes": None},
                steps_done=1,
            ))
    finally:
        shutil.rmtree(work, ignore_errors=True)
    best = min(commits)
    return {
        "commit_s": round(best, 3),
        "commits_s": [round(c, 3) for c in commits],
        "deadline_s": deadline_s,
        "ok": best <= deadline_s,
    }


def bench_torch_cpu(batches, steps: int):
    """Same-semantics torch-CPU inference baseline (real graphs/sec)."""
    import torch

    from deepdfa_tpu.compat.torch_ref import TorchGGNN
    from deepdfa_tpu.config import FeatureConfig

    torch.manual_seed(0)
    model = TorchGGNN(FeatureConfig().input_dim).eval()
    prepped = []
    for b in batches:
        n_nodes = int(b.node_mask.sum())
        n_edges = int(b.edge_mask.sum())
        n_graphs = int(b.graph_mask.sum())
        feats = {
            k: torch.tensor(np.asarray(v[:n_nodes], dtype=np.int64))
            for k, v in b.node_feats.items()
            if k.startswith("_ABS_DATAFLOW")
        }
        prepped.append(
            (
                feats,
                torch.tensor(np.asarray(b.senders[:n_edges], np.int64)),
                torch.tensor(np.asarray(b.receivers[:n_edges], np.int64)),
                torch.tensor(np.asarray(b.node_gidx[:n_nodes], np.int64)),
                n_graphs,
            )
        )
    with torch.no_grad():
        model(*prepped[0])  # warmup
        t0 = time.perf_counter()
        for i in range(steps):
            model(*prepped[i % len(prepped)])
        dt = time.perf_counter() - t0
    mean_graphs = float(np.mean([p[4] for p in prepped]))
    return steps * mean_graphs / dt


def _validate(name: str, graphs_per_sec, flops_per_step, real_graphs, roofline, refused):
    """Refuse any throughput whose implied FLOP/s exceeds the measured
    roofline — it is a timing artifact, not throughput."""
    if graphs_per_sec is None:
        return None
    if flops_per_step and roofline:
        implied = graphs_per_sec / real_graphs * flops_per_step
        if implied > roofline:
            refused[name] = (
                f"implied {implied / 1e12:.1f} TFLOP/s > measured roofline "
                f"{roofline / 1e12:.1f} TFLOP/s"
            )
            return None
    return round(graphs_per_sec, 1)


import functools


@functools.lru_cache(maxsize=1)
def _git_provenance() -> tuple:
    """Code provenance for every artifact: ``(full_commit_hash, dirty)``.

    The old ``git describe`` path silently emitted ``git_rev: null`` on the
    bench hosts (no ``git`` on PATH / ownership-untrusted clones), which
    made whole artifact trajectories unattributable. Three tiers, all
    failure-tolerant:

    1. ``git rev-parse HEAD`` + ``git status --porcelain`` (with
       ``safe.directory=*`` so root-owned CI clones don't trip the
       dubious-ownership refusal); dirty = any non-empty status line.
    2. No usable git binary: parse ``.git/HEAD`` (+ the ref file /
       ``packed-refs``) by hand — hash-only, ``dirty=None`` (unknown).
    3. Nothing readable: ``(None, None)`` — still never raises.
    """
    import os
    import subprocess

    repo = os.path.dirname(os.path.abspath(__file__))

    def _run(*args):
        out = subprocess.run(
            ["git", "-C", repo, "-c", "safe.directory=*", *args],
            capture_output=True, text=True, timeout=10)
        if out.returncode != 0:
            raise RuntimeError(out.stderr.strip())
        return out.stdout

    try:
        rev = _run("rev-parse", "HEAD").strip() or None
        if rev is None:
            raise RuntimeError("empty rev-parse output")
        try:
            dirty = bool(_run("status", "--porcelain").strip())
        except Exception:
            dirty = None
        return rev, dirty
    except Exception:
        pass
    try:
        head = open(os.path.join(repo, ".git", "HEAD")).read().strip()
        if head.startswith("ref:"):
            ref = head.split(None, 1)[1]
            ref_path = os.path.join(repo, ".git", *ref.split("/"))
            if os.path.exists(ref_path):
                return open(ref_path).read().strip() or None, None
            packed = os.path.join(repo, ".git", "packed-refs")
            if os.path.exists(packed):
                for line in open(packed):
                    if line.strip().endswith(" " + ref) or line.strip().endswith(ref):
                        parts = line.split()
                        if len(parts) == 2 and parts[1] == ref:
                            return parts[0], None
            return None, None
        return head or None, None
    except Exception:
        return None, None


def _git_rev() -> str | None:
    """Back-compat shim (scripts/bench_int8_llm.py): hash with a ``-dirty``
    suffix when the worktree had uncommitted changes."""
    rev, dirty = _git_provenance()
    if rev is None:
        return None
    return f"{rev}-dirty" if dirty else rev


def _provenance_fields() -> dict:
    """The attribution block EVERY artifact assembler must spread into its
    result: full commit hash + dirty flag (``git_dirty`` None = unknown,
    e.g. hash recovered from ``.git/HEAD`` without a git binary) and the
    emission wall clock (file mtimes reset on checkout/clone).
    ``schema_version``
    stamps the artifact shape so downstream readers (the perf-regression
    ledger) can evolve their parsers without guessing; the ledger also
    tolerates the pre-versioned artifacts already in the repo root."""
    rev, dirty = _git_provenance()
    return {
        "schema_version": 1,
        "git_rev": rev,
        "git_dirty": dirty,
        "emitted_at_unix": int(time.time()),
    }


def _nominal_peak_tflops(device_kind: str) -> float:
    """Datasheet bf16 peak for the ``device_kind`` JAX reports. An unknown
    kind raises: a silent ``None`` turned every nominal-MFU column null and
    let a run on the wrong device exit 0."""
    try:
        return NOMINAL_BF16_TFLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no datasheet peak for device_kind {device_kind!r} — known: "
            f"{sorted(NOMINAL_BF16_TFLOPS)}; add the row (with its source) "
            "to NOMINAL_BF16_TFLOPS before benchmarking on it") from None


GOLDEN_CONFIG = "hidden32_steps5_concat4_batch256"


def _derived_columns(value, flops_per_graph, roofline_tflops,
                     nominal_tflops, base_gps, a100_gps) -> dict:
    """The headline's derived columns — implied TFLOP/s, MFU (measured +
    nominal), baseline and A100 ratios."""
    implied = (value * flops_per_graph / 1e12
               if (value is not None and flops_per_graph) else None)
    return {
        "implied_tflops": round(implied, 2) if implied is not None else None,
        "mfu": (round(implied / roofline_tflops, 4)
                if (implied is not None and roofline_tflops) else None),
        "mfu_nominal": (round(implied / nominal_tflops, 4)
                        if (implied is not None and nominal_tflops) else None),
        "vs_baseline": (round(value / base_gps, 2)
                        if (value is not None and base_gps) else None),
        "est_vs_a100": (round(value / a100_gps, 4)
                        if (value is not None and a100_gps) else None),
        "est_vs_a100_8chip_dp": (round(8 * value / a100_gps, 4)
                                 if (value is not None and a100_gps) else None),
    }


def _assemble_result(backend, device_kind, roofline, occupancy, real_graphs,
                     chained, dense=None, dense_real=None, dense_occ=None,
                     dense_dropped=None, dense_error=None, chained_train=None,
                     strict=None, peak_runs=None,
                     base_gps=None, fused=None,
                     fused_real=None, fused_error=None,
                     fused_batch_graphs=None):
    """Build the ONE-line artifact from the stages that ran (a stage the
    ``--layout`` focus skipped is ``None`` and reports null columns)."""
    peak_runs = peak_runs or {}
    refused: dict[str, str] = {}
    seg_value = _validate("segment_graphs_per_sec", chained["graphs_per_sec"],
                          chained["flops_per_step"], real_graphs, roofline, refused)
    dense_value = None
    if dense is not None:
        dense_value = _validate("dense_graphs_per_sec", dense["graphs_per_sec"],
                                dense["flops_per_step"], dense_real, roofline,
                                refused)
    fused_value = None
    if fused is not None:
        fused_value = _validate("fused_graphs_per_sec", fused["graphs_per_sec"],
                                fused["flops_per_step"], fused_real, roofline,
                                refused)
    # Headline: the fastest of the validated layouts of the SAME model
    # (identical parameters; parity-tested forwards).
    value, layout = seg_value, "segment"
    head_flops_per_graph = (
        chained["flops_per_step"] / real_graphs
        if chained["flops_per_step"] else None
    )
    if dense_value is not None and (value is None or dense_value > value):
        value, layout = dense_value, "dense_adjacency"
        head_flops_per_graph = (
            dense["flops_per_step"] / dense_real
            if dense["flops_per_step"] else None
        )
    if fused_value is not None and (value is None or fused_value > value):
        value, layout = fused_value, "fused"
        head_flops_per_graph = (
            fused["flops_per_step"] / fused_real
            if fused["flops_per_step"] else None
        )
    # Full layout trajectory for the re-anchor reviewer: RAW measured rates
    # (pre-refusal) beside the validated ones, so a losing or refused
    # layout's number survives in the artifact instead of being discarded.
    layout_compare = {}
    for name, run, validated in (("segment", chained, seg_value),
                                 ("dense_adjacency", dense, dense_value),
                                 ("fused", fused, fused_value)):
        if run is not None:
            layout_compare[name] = {
                "graphs_per_sec_raw": round(run["graphs_per_sec"], 1),
                "graphs_per_sec": validated,
            }
    layout_compare["winner"] = layout if value is not None else None
    train_gps = strict_gps = None
    if chained_train is not None:
        train_gps = _validate("train_graphs_per_sec", chained_train["graphs_per_sec"],
                              chained_train["flops_per_step"], real_graphs, roofline, refused)
    if strict is not None:
        strict_gps = _validate("strict_graphs_per_sec", strict["graphs_per_sec"],
                               strict["flops_per_step"], real_graphs, roofline, refused)
    peak_by_size: dict[str, float | None] = {}
    for bg, (p, pr) in peak_runs.items():
        peak_by_size[bg] = _validate(f"peak_batch{bg}_graphs_per_sec",
                                     p["graphs_per_sec"], p["flops_per_step"],
                                     pr, roofline, refused)
    peak_valid = [v for v in peak_by_size.values() if v is not None]
    peak_gps = max(peak_valid) if peak_valid else None

    nominal = _nominal_peak_tflops(device_kind)
    # North-star bound: what 1×A100 would do on the same model at a generous
    # MFU. The A100/DGL reference runs ragged SPARSE batches, paying only
    # real-graph segment-layout FLOPs — so its per-graph cost is the segment
    # path's, excluding our padding share (and never the dense layout's
    # deliberately larger n² matmul FLOPs).
    real_flops_per_graph = (
        (chained["flops_per_step"] or 0.0) / real_graphs * occupancy["nodes"]
    )
    a100_est_gps = (
        A100_BF16_PEAK_TFLOPS * 1e12 * A100_ASSUMED_MFU / real_flops_per_graph
        if real_flops_per_graph else None
    )

    derived = _derived_columns(value, head_flops_per_graph, roofline / 1e12,
                               nominal, base_gps, a100_est_gps)
    result = {
        "metric": "ggnn_inference_graphs_per_sec",
        "value": value,
        "unit": "graphs/sec",
        "vs_baseline": derived["vs_baseline"],
        "backend": backend,
        "device_kind": device_kind,
        "dtype": "bfloat16",
        "layout": layout,
        "timing": (
            f"chained: one jitted scan over k={chained['k']} device-resident "
            "batches, scalar readback depends on every step; best of 3; "
            "headline = fastest of segment / dense-adjacency / fused-VMEM "
            "layouts (same parameters, parity-tested forwards)"
        ),
        "segment_graphs_per_sec": seg_value,
        "step_ms": round(chained["step_ms"], 3),
        "chain_wall_s": round(chained["wall_s"], 3),
        "flops_per_step": chained["flops_per_step"],
        "dense_graphs_per_sec": dense_value,
        "dense_step_ms": round(dense["step_ms"], 3) if dense else None,
        "dense_flops_per_step": dense["flops_per_step"] if dense else None,
        "dense_shapes": dense["shapes"] if dense else None,
        "dense_graphs_per_step": (
            round(dense["graphs_per_step"], 1) if dense else None
        ),
        "dense_occupancy": (
            {k: round(v, 3) for k, v in dense_occ.items()} if dense_occ else None
        ),
        "dense_dropped_oversize": dense_dropped,
        "dense_error": dense_error,
        # per-shape dense rates — diagnostic only
        # (a partial mixture must never be quoted as the dense headline:
        # it would drop the large-graph shapes and inflate the rate)
        "dense_by_shape": dense.get("by_shape") if dense else None,
        # fused-VMEM Pallas layout (ops/fused_ggnn.py): measured on VMEM-
        # sized buckets (fused_batch_graphs per batch), real graphs counted
        "fused_graphs_per_sec": fused_value,
        "fused_step_ms": round(fused["step_ms"], 3) if fused else None,
        "fused_flops_per_step": fused["flops_per_step"] if fused else None,
        "fused_graphs_per_batch": (
            round(fused_real, 1) if fused_real else None
        ),
        "fused_batch_graphs": fused_batch_graphs,
        "fused_error": fused_error,
        "layout_compare": layout_compare,
        "implied_tflops": derived["implied_tflops"],
        "roofline_tflops": round(roofline / 1e12, 1),
        "roofline_note": ("parallel independent bf16 matmul chains — the "
                          "ceiling reachable in-process; mfu = fraction of it"),
        "mfu": derived["mfu"],
        "mfu_nominal": derived["mfu_nominal"],
        "nominal_peak_tflops": nominal,
        "padding_efficiency": {k: round(v, 3) for k, v in occupancy.items()},
        "graphs_per_batch": round(real_graphs, 1),
        "strict_graphs_per_sec": strict_gps,
        "strict_step_ms": round(strict["step_ms"], 3) if strict else None,
        "pipelined_graphs_per_sec": (
            round(strict["pipelined_graphs_per_sec"], 1) if strict else None
        ),
        "train_graphs_per_sec": train_gps,
        "train_step_ms": (
            round(chained_train["step_ms"], 3) if chained_train else None
        ),
        "peak_superbatch_graphs_per_sec": peak_gps,
        "peak_by_batch": peak_by_size or None,
        "refused": refused or None,
        "baseline": "torch-cpu same-semantics GGNN (compat/torch_ref.py)",
        "baseline_graphs_per_sec": round(base_gps, 1) if base_gps else None,
        "est_a100_graphs_per_sec": round(a100_est_gps, 1) if a100_est_gps else None,
        "est_vs_a100": derived["est_vs_a100"],
        # the north star (BASELINE.json) is a v4-8 SLICE (8 chips) vs ONE
        # A100; inference dp is embarrassingly parallel here (a graph never
        # spans chips, no cross-chip collectives in the forward), so the
        # 8-chip estimate is single-chip × 8 — stated as the derivation it is
        "est_vs_a100_8chip_dp": derived["est_vs_a100_8chip_dp"],
        "a100_assumption": f"{A100_BF16_PEAK_TFLOPS:.0f} TFLOP/s bf16 peak × {A100_ASSUMED_MFU} MFU",
        "a100_assumption_note": (
            f"{A100_ASSUMED_MFU:.0%} MFU is GENEROUS to the A100: DGL GNN "
            "inference at hidden-32 is gather/scatter-bound on GPUs too, "
            "with typical MFU well under 5% — the ratio is a lower bound"
        ),
        "config": GOLDEN_CONFIG,
        **_provenance_fields(),
    }
    return result


def _peak_list(spec: str) -> tuple:
    """argparse type for ``--peak-batches``: a malformed value is a usage
    error (exit 2), not a crash mid-run."""
    try:
        return tuple(int(s) for s in spec.split(",") if s.strip())
    except ValueError as e:
        raise argparse.ArgumentTypeError(str(e))


def _build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--chain", type=int, default=128,
                    help="k batches per chained-scan dispatch (headline)")
    ap.add_argument("--baseline-steps", type=int, default=20)
    ap.add_argument("--batches", type=int, default=4)
    ap.add_argument("--skip-baseline", action="store_true")
    ap.add_argument("--peak-batches", type=_peak_list, default="1024",
                    help="comma-separated superbatch sizes for the peak "
                    "stage ('' skips it). 2048 is opt-in: its ~113k-node "
                    "unrolled compile has never completed on a chip.")
    ap.add_argument("--layout", choices=("both", "segment", "dense", "fused"),
                    default="both",
                    help="segment: skip the dense-adjacency and fused stages; "
                    "dense: roofline + segment anchor + dense only (no train/"
                    "strict/superbatch/baseline); fused: roofline + segment "
                    "anchor + fused-VMEM Pallas stage only. A stage that "
                    "raises ends the run non-zero, so the focused modes let "
                    "an operator measure each layout in its own run.")
    return ap


# VMEM-sized batch for the fused stage: the golden 256-graph bucket's
# working set (~108 MiB at hidden width 128) is over the fused kernel's
# conservative 96 MiB plan, so the fused stage packs the SAME corpus at
# half the graphs per batch (~57 MiB — comfortable headroom). graphs/sec
# on real graphs stays directly comparable across layouts.
FUSED_BATCH_GRAPHS = 128


def main(argv=None):
    args = _build_parser().parse_args(argv)
    dense_focus = args.layout == "dense"
    fused_focus = args.layout == "fused"

    from deepdfa_tpu.config import FeatureConfig

    backend, device_kind = start_on_device()
    nominal_tflops = _nominal_peak_tflops(device_kind)  # unknown kind raises

    _progress("building corpus batches (host)")
    # corpus sized for the largest consumer among the stages this --layout
    # actually runs (the focused modes skip the superbatch peaks, so they
    # don't pay their host-side corpus construction)
    peak_max = max(args.peak_batches, default=0)
    n_corpus = (int(args.batches * 256 * 1.5 * 2)
                if (dense_focus or fused_focus)
                else max(int(2 * peak_max * 1.5),
                         int(args.batches * 256 * 1.5 * 2)))
    corpus = build_corpus(n_corpus, FeatureConfig().input_dim)
    batches, occupancy = build_batches(corpus, args.batches)
    real_graphs = float(np.mean([int(b.graph_mask.sum()) for b in batches]))

    _progress("measuring roofline")
    roofline = measure_roofline()
    _progress(f"roofline {roofline / 1e12:.1f} TFLOP/s; chained inference (k={args.chain})")
    chained = bench_chained(batches, args.chain, train=False)
    _progress(f"chained: {chained['graphs_per_sec']:.0f} g/s")
    dense = dense_occ = dense_real = None
    dense_error = dense_dropped = None
    fused = fused_real = fused_error = None
    chained_train = strict = sentinel_stats = emergency_stats = None
    fused_train_stats = int8_serving_stats = strict_latency_stats = None
    megabatch_stats = None
    peak_runs: dict[str, tuple] = {}

    # A stage that raises ends the run non-zero: an artifact with an
    # "error" field under a green exit code reads as a measurement.
    skip_base = args.skip_baseline or dense_focus or fused_focus
    _progress("torch-cpu baseline (skipped)" if skip_base
              else "torch-cpu baseline")
    base_gps = None if skip_base else bench_torch_cpu(batches, args.baseline_steps)
    if not (dense_focus or fused_focus):
        _progress("chained train")
        chained_train = bench_chained(batches, max(args.chain // 4, 8), train=True)
        _progress("single-dispatch strict/pipelined")
        strict = bench_jax(batches, args.steps, train=False)
        # Resilience invariant guard: the divergence sentinel must cost
        # < 2% of a train step (its isfinite+select fuses into the update).
        # An overrun is recorded (ok: false), not fatal — timing is noisy.
        _progress("sentinel overhead")
        sentinel_stats = bench_sentinel_overhead(
            batches, steps=max(args.steps // 2, 10))
        if not sentinel_stats["ok"]:
            _progress(
                f"WARNING: sentinel overhead "
                f"{sentinel_stats['overhead_pct']:.1f}% exceeds the 2% "
                "budget")
        # Resilience invariant guard #2: the SIGTERM emergency checkpoint
        # must commit within the preempt_deadline_s grace budget — a real
        # model state through the atomic save path, timed end-to-end.
        _progress("emergency-checkpoint commit latency")
        emergency_stats = bench_emergency_ckpt(batches)
        if not emergency_stats["ok"]:
            _progress(
                f"WARNING: emergency checkpoint commit "
                f"{emergency_stats['commit_s']:.1f}s exceeds the "
                f"{emergency_stats['deadline_s']:.0f}s preemption budget")

    # Peak throughput at superbatches: same model, larger static batches -
    # bigger kernels per dispatch, higher arithmetic intensity.
    for bg in () if (dense_focus or fused_focus) else args.peak_batches:
        _progress(f"superbatch-{bg} peak")
        peak_batches, _ = build_batches(corpus, 2, batch_graphs=bg)
        pr = float(np.mean([int(b.graph_mask.sum()) for b in peak_batches]))
        peak_runs[str(bg)] = (
            bench_chained(peak_batches, max(args.chain // 4, 8), train=False),
            pr,
        )

    # Fused-VMEM Pallas stage (ops/fused_ggnn.py): same corpus packed at
    # VMEM-sized buckets (FUSED_BATCH_GRAPHS graphs/batch — the golden
    # 256-graph bucket's working set exceeds the kernel's 96 MiB plan).
    if args.layout in ("segment", "dense"):
        fused_error = f"skipped (--layout {args.layout})"
    else:
        _progress("fused-VMEM Pallas chained")
        from deepdfa_tpu.config import GGNNConfig
        from deepdfa_tpu.ops.fused_ggnn import fits_vmem

        fused_batches, _focc = build_batches(
            corpus, args.batches, batch_graphs=FUSED_BATCH_GRAPHS)
        fb = fused_batches[0]
        width = GGNNConfig().out_dim // 2
        if not fits_vmem(fb.max_nodes, fb.senders.shape[0], width):
            raise RuntimeError(
                f"fused bucket ({fb.max_nodes} nodes, "
                f"{fb.senders.shape[0]} edges, width {width}) exceeds "
                "the kernel's VMEM plan — shrink FUSED_BATCH_GRAPHS")
        fused = bench_chained(fused_batches, args.chain, train=False,
                              layout="fused")
        fused_real = float(np.mean(
            [int(b.graph_mask.sum()) for b in fused_batches]))
        _progress(f"fused: {fused['graphs_per_sec']:.0f} g/s")

        # Fused TRAIN step (the dispatch-gap tentpole): one jitted dispatch
        # per batch covering forward + Pallas recompute-backward + optimizer
        # update, gated at <= 0.8x the segment train step on the same data.
        _progress("fused train step (ggnn_fused_train)")
        ft_fused, ft_seg, ft_bg = bench_fused_train(
            corpus, min(args.batches, 2), max(args.chain // 4, 8))
        fused_train_stats = assemble_fused_train_result(
            backend, device_kind, ft_fused, ft_seg, ft_bg)
        _progress(
            f"fused train: {ft_fused['step_ms']:.2f} ms vs segment "
            f"{ft_seg['step_ms']:.2f} ms "
            f"(ratio {fused_train_stats['ratio_vs_segment']})")

        # Megabatch packing + whole-model fusion: many buckets' graphs in
        # ONE launch per packed megabatch (embed through label head), vs
        # the per-bucket ladder's dispatch count on the same graphs. The
        # frozen-int8-conv training experiment rides on the same packed
        # batches and nests under this block (ledger series
        # ggnn_megabatch.int8_train).
        _progress("megabatch whole-model chained (ggnn_megabatch)")
        mb_run, mb_pack, mb_ladder, mb_int8 = bench_megabatch(
            corpus, args.batches * 256, args.chain, int8_steps=4)
        megabatch_stats = assemble_megabatch_result(
            backend, device_kind, mb_run, mb_pack, mb_ladder,
            roofline, nominal_tflops, int8_train=mb_int8)
        _progress(
            f"megabatch: {mb_run['graphs_per_sec']:.0f} g/s, "
            f"{megabatch_stats['dispatches_per_step']} dispatches vs "
            f"ladder {mb_ladder}, mfu={megabatch_stats['mfu']}, "
            f"ceiling={megabatch_stats['ceiling']}")

    if args.layout == "both":
        # Serving-precision gate: int8 conv matmuls vs f32, tier p50/p99
        # both ways; refusal-with-fallback counts as the gate WORKING.
        _progress("int8 serving path (int8_serving)")
        int8_serving_stats = assemble_int8_serving_result(
            backend, device_kind, **bench_int8_serving(corpus))
        _progress(
            f"int8 serving: precision={int8_serving_stats['value']} "
            f"delta={int8_serving_stats['int8_score_delta']}")

        # Warm device-resident engine loop: donated-buffer submits with
        # LATENCY_WINDOW_DEPTH in flight vs per-request strict sync.
        _progress("latency-mode engine loop (strict_latency)")
        sl_strict, sl_latency = bench_strict_latency(corpus)
        strict_latency_stats = assemble_strict_latency_result(
            backend, device_kind, sl_strict, sl_latency,
            LATENCY_WINDOW_DEPTH, 64)
        _progress(
            f"strict {sl_strict:.2f} ms vs latency-mode "
            f"{sl_latency:.2f} ms per request "
            f"(ratio {strict_latency_stats['ratio_vs_strict']})")

    # Dense-adjacency LAST: its per-shape compiles of the n^2 forward are
    # the longest in the run.
    if args.layout in ("segment", "fused"):
        dense_error = f"skipped (--layout {args.layout})"
    else:
        _progress("dense-adjacency chained")
        dense_groups, dense_occ, dense_dropped = build_dense_batches(
            corpus, args.batches
        )
        dense = bench_chained_dense(
            dense_groups, args.chain,
            on_shape=lambda done: _progress(
                f"dense shape done: {sorted(done)}"))
        dense_real = dense["graphs_per_step"]
        _progress(f"dense: {dense['graphs_per_sec']:.0f} g/s "
                  f"(shapes {dense['shapes']})")

    result = _assemble_result(
        backend, device_kind, roofline, occupancy, real_graphs, chained,
        dense, dense_real, dense_occ, dense_dropped, dense_error,
        chained_train, strict, peak_runs, base_gps,
        fused, fused_real, fused_error, FUSED_BATCH_GRAPHS)
    if sentinel_stats is not None:
        result["sentinel"] = sentinel_stats
    if emergency_stats is not None:
        result["emergency_ckpt"] = emergency_stats
    if fused_train_stats is not None:
        result["fused_train"] = fused_train_stats
    if int8_serving_stats is not None:
        result["int8_serving"] = int8_serving_stats
    if strict_latency_stats is not None:
        result["strict_latency"] = strict_latency_stats
    if megabatch_stats is not None:
        result["ggnn_megabatch"] = megabatch_stats
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
