#!/usr/bin/env python
"""Hierarchical whole-unit scoring: level-1 fused embeddings + call-graph
composition, cold vs warm through the function-embedding cache.

The ``hier`` ledger stage (``bench.assemble_hier_result``). A seeded
multi-function corpus (cross-function taint chains — the shape only the
supergraph connects) is scored as ONE unit by the two-level scorer
(``models/ggnn_hier.py``): level 1 embeds every function through the
fused megabatch encoder, level 2 composes the unit score over the call
graph. The run is then repeated warm — same content, a fresh
:class:`~deepdfa_tpu.serve.embcache.FunctionEmbeddingCache` handle over
the SAME populated cache root — and the artifact gates on the structural
invariants of the design, not just the timing:

- ``fallback_dispatches == 0`` (both passes): whole-program scoring
  never leaves the fused megabatch kernels — no segment fallback, ever;
- warm ``level1_recompute == 0`` and ``embed_cache_hit_rate == 1.0``:
  a warm re-scan of unchanged functions re-embeds NOTHING;
- the unit score is bit-identical cold vs warm (a cache that changes
  the answer is a bug, not a cache);
- ``warm_speedup >= 1``: skipping level 1 must not cost more than
  running it.

Pure host-side by default (CPU interpret-mode kernels); prints ONE JSON
line.

Usage: python scripts/bench_hier.py [--chains 8] [--reps 3]
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def _chain_units(n_chains: int) -> list[str]:
    """Seeded 3-function taint chains (source in ``root_j``, sink two
    calls down in ``leaf_j``) — same corpus shape as the ``interproc``
    stage, so the two artifacts measure the same workload."""
    units = []
    for j in range(n_chains):
        units.append(f"""
int leaf_{j}(char *data) {{ char local[64]; strcpy(local, data); return local[0]; }}
int mid_{j}(char *buf) {{ int r; r = leaf_{j}(buf); return r; }}
int root_{j}(void) {{ char buf[64]; int r; gets(buf); r = mid_{j}(buf); return r; }}
""")
    return units


def _build_vocabs():
    from deepdfa_tpu.config import FeatureConfig
    from deepdfa_tpu.cpg.features import add_dependence_edges
    from deepdfa_tpu.cpg.frontend import parse_source
    from deepdfa_tpu.data.codegen import demo_corpus
    from deepdfa_tpu.data.materialize import CorpusBuilder

    rows = demo_corpus(6, seed=0).to_dict("records")
    cpgs = {int(r["id"]): add_dependence_edges(parse_source(r["before"]))
            for r in rows}
    labels = {int(r["id"]): int(r["vul"]) for r in rows}
    _, vocabs = CorpusBuilder(FeatureConfig()).build(
        cpgs, list(cpgs), graph_labels=labels)
    return vocabs


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chains", type=int, default=8,
                    help="number of 3-function taint chains in the unit")
    ap.add_argument("--reps", type=int, default=3,
                    help="timing repetitions for the warm pass")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import assemble_hier_result, start_on_device
    from deepdfa_tpu.config import GGNNConfig
    from deepdfa_tpu.cpg.interproc import build_supergraph, merge_cpgs
    from deepdfa_tpu.data.graphs import Graph, batch_np
    from deepdfa_tpu.data.vocab import ALL_SUBKEYS
    from deepdfa_tpu.models import make_model
    from deepdfa_tpu.models.ggnn_hier import HierScorer, UnitFunction
    from deepdfa_tpu.pipeline import encode_source
    from deepdfa_tpu.serve.embcache import FunctionEmbeddingCache

    backend, device_kind = start_on_device()
    vocabs = _build_vocabs()
    units = _chain_units(args.chains)

    # the golden megabatch-compatible config at bench-friendly width
    cfg = GGNNConfig(hidden_dim=8, n_steps=2, num_output_layers=2)
    keys = tuple(f"_ABS_DATAFLOW_{sk}" for sk in ALL_SUBKEYS)
    model = make_model(cfg, input_dim=40)
    g = Graph(senders=np.arange(3, dtype=np.int32),
              receivers=np.arange(1, 4, dtype=np.int32),
              node_feats={k: np.zeros(4, np.int32) for k in keys},
              ).with_self_loops()
    example = jax.tree.map(jnp.asarray, batch_np([g], 2, 8, 128))
    params = model.init(jax.random.key(0), example)["params"]

    # one merged translation unit: supergraph + per-function graphs
    per_unit_cpgs = [encode_source(u, vocabs, keep_cpg=True) for u in units]
    merged, _ = merge_cpgs(
        [fn.cpg for fns in per_unit_cpgs for fn in fns if fn.cpg is not None])
    sg = build_supergraph(merged)
    # name-prefix the per-function cache content: functions sharing a
    # translation unit must not collide on one embedding-cache key
    unit_fns = [UnitFunction(fn.name, f"{fn.name}\n{u}", fn.graph)
                for u, fns in zip(units, per_unit_cpgs)
                for fn in fns if fn.graph is not None]

    error = None
    with tempfile.TemporaryDirectory() as td:
        cache_root = Path(td) / "emb"

        def scorer(cache):
            return HierScorer(cfg, model.input_dim, params,
                              cache=cache, model_rev="bench_hier")

        def emb_cache():
            return FunctionEmbeddingCache(cache_root, model_rev="bench_hier",
                                          vocab_hash="bench", dim=None)

        # cold: empty cache root, every function embeds through level 1
        cold = scorer(emb_cache())
        t0 = time.perf_counter()
        cold_out = cold.score_unit(unit_fns, sg)
        cold_ms = (time.perf_counter() - t0) * 1e3
        dispatches_cold = cold.n_level1_dispatches
        fallbacks = cold.n_fallback_dispatches

        # warm: fresh handle over the SAME populated root — zero-embed pass
        warm_cache = emb_cache()
        warm = scorer(warm_cache)
        reps = max(1, args.reps)
        t0 = time.perf_counter()
        for _ in range(reps):
            warm_out = warm.score_unit(unit_fns, sg)
        warm_ms = (time.perf_counter() - t0) / reps * 1e3
        fallbacks += warm.n_fallback_dispatches

        hit_rate = warm_cache.stats()["hit_rate"]
        recompute = warm.level1_recompute
        score = cold_out["unit_score"]
        if warm_out["unit_score"] != score:
            error = (f"unit score diverged warm: {score} != "
                     f"{warm_out['unit_score']}")
            score = None

    result = assemble_hier_result(
        n_functions=len(unit_fns),
        n_call_edges=sg.n_call_edges,
        cold_unit_score_ms=cold_ms,
        warm_unit_score_ms=warm_ms,
        embed_cache_hit_rate=hit_rate,
        level1_recompute=recompute,
        fallback_dispatches=fallbacks,
        level1_dispatches_cold=dispatches_cold,
        unit_score=score,
        error=error,
    )
    result["n_chains"] = args.chains
    result["reps"] = reps
    result["backend"] = backend
    result["device_kind"] = device_kind
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    # the JSON line carries the measured numbers; the exit code carries ok
    raise SystemExit(0 if main()["ok"] else 1)
