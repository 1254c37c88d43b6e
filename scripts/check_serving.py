#!/usr/bin/env python
"""Serving-artifact round-trip check on the local accelerator.

Default mode: exports the GGNN scoring forward (fresh params — this
validates the SERIALIZATION contract, which is training-independent),
deserializes it, and calls it on a real random batch on whatever backend
jax finds, comparing against the live ``model.apply``. On the TPU this
is the proof that the cpu+tpu-lowered StableHLO artifact
(`deepdfa_tpu/serving.py`) actually executes on the chip — the CPU suite
can only check the cpu leg.

``--artifact DIR`` mode: validates a PRE-EXPORTED artifact dir instead —
manifest completeness, deserialization, and one real call at the
manifest's exact shapes; ``ok`` asserts the masked outputs are finite
probabilities in [0, 1] (no reference params exist for a foreign
artifact, so there is no diff to compare — the gate is "this directory
is deployable", the pre-ship check ``deepdfa-tpu serve --artifact``
operators run).

Prints ONE JSON line: ``{metric, value, unit, vs_baseline, backend, ok}``.
"""

from __future__ import annotations

import json
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

TOL = 2e-4  # bf16-model probabilities re-lowered per backend

_MANIFEST_REQUIRED = ("format", "label_style", "node_feat_keys",
                      "input_leaves", "platforms")


def check_artifact(artifact_dir: str, backend: str, device_kind: str) -> dict:
    """Load + call a pre-exported artifact at its own manifest shapes."""
    import numpy as np

    from deepdfa_tpu.data.graphs import Graph, batch_np
    from deepdfa_tpu.serving import load_exported

    servable = load_exported(artifact_dir)
    man = servable.manifest
    missing = [k for k in _MANIFEST_REQUIRED if k not in man]
    # flatten order: node_feats (sorted keys), senders, receivers,
    # node_gidx, node_mask, edge_mask, graph_mask
    leaves = man["input_leaves"]
    max_graphs = int(leaves[-1]["shape"][0])
    max_edges = int(leaves[-2]["shape"][0])
    max_nodes = int(leaves[-3]["shape"][0])

    n = 6
    feats = {k: np.zeros(n, np.int32) for k in man["node_feat_keys"]}
    g = Graph(senders=np.arange(n - 1, dtype=np.int32),
              receivers=np.arange(1, n, dtype=np.int32),
              node_feats=feats).with_self_loops()
    batch = batch_np([g], max_graphs, max_nodes, max_edges)
    out = np.asarray(servable(batch), np.float32)
    mask = np.asarray(batch.node_mask if man["label_style"] == "node"
                      else batch.graph_mask)
    real = out[mask]
    in_range = bool(np.all(np.isfinite(real))
                    and np.all(real >= 0.0) and np.all(real <= 1.0))
    value = float(np.max(real)) if real.size else float("nan")
    return {
        "metric": "serving_artifact_valid",
        "value": value,
        "unit": "probability",
        "vs_baseline": None,
        "backend": backend,
        "device_kind": device_kind,
        "artifact": str(artifact_dir),
        "label_style": man["label_style"],
        "shapes": {"max_graphs": max_graphs, "max_nodes": max_nodes,
                   "max_edges": max_edges},
        "vocab_hash": man.get("vocab_hash"),
        "manifest_missing": missing,
        "ok": in_range and not missing and real.size > 0,
    }


def main(argv=None) -> dict:
    import argparse

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepdfa_tpu.config import ExperimentConfig
    from deepdfa_tpu.data.graphs import BucketSpec, GraphBatcher
    from deepdfa_tpu.data.synthetic import random_dataset
    from deepdfa_tpu.models import make_model
    from deepdfa_tpu.serving import example_batch, export_ggnn, load_exported

    ap = argparse.ArgumentParser()
    ap.add_argument("--artifact", default=None,
                    help="validate this pre-exported artifact dir instead "
                    "of the export round-trip")
    args = ap.parse_args(argv)

    from bench import start_on_device

    backend, device_kind = start_on_device()
    if args.artifact:
        result = check_artifact(args.artifact, backend, device_kind)
        print(json.dumps(result))
        return result

    cfg = ExperimentConfig()
    model = make_model(cfg.model, cfg.input_dim)
    ex = jax.tree.map(jnp.asarray, example_batch(cfg))
    params = model.init(jax.random.key(0), ex)["params"]

    with tempfile.TemporaryDirectory(prefix="serving-check-") as tmp:
        servable = load_exported(export_ggnn(cfg, params, tmp))
        b = cfg.data.batch
        batcher = GraphBatcher(
            [BucketSpec(b.batch_graphs + 1, b.max_nodes, b.max_edges)])
        batch = next(iter(batcher.batches(
            random_dataset(128, seed=11, input_dim=cfg.input_dim))))
        got = servable(batch)
        want = np.asarray(jax.nn.sigmoid(model.apply(
            {"params": params}, jax.tree.map(jnp.asarray, batch))))
        mask = np.asarray(batch.graph_mask)
        diff = float(np.max(np.abs(got[mask] - want[mask])))

    result = {
        "metric": "serving_roundtrip_max_abs_diff",
        "value": diff,
        "unit": "probability",
        "vs_baseline": None,
        "backend": backend,
        "device_kind": device_kind,
        "tolerance": TOL,
        "ok": diff <= TOL,
    }
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    # the JSON line carries the measured diff; the exit code carries ok
    raise SystemExit(0 if main()["ok"] else 1)
