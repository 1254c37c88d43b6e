"""Traffic: data files of parameters, read by generators found by name.

A traffic mix is a data file ``benchmark/traffic/<mix>.json``: a ``generator``
name and that generator's parameters. A generator is
``benchmark/generators/<generator>.py`` with ``generate(params, seed)``; a
cell whose traffic an existing generator can make adds a data file and
nothing else. The program never sees any of this — a driver turns what a
generator returns (plain numpy arrays) into the program's own input types,
and the plain reference reads the same arrays by row index.

Sizes (token lengths, node counts) are drawn from ``size_seed``, a constant of
the mix, and belong to the row index: row ``i`` has the same length and the
same node count under every ``--seed``, with other contents (token ids,
features, shortcut edges). The seed changes the data, not the amount
of work — a window reaches only a few percent of an epoch, so sizes permuted by
the seed made runs of different seeds differ by 3% where two runs of one seed
differed by 0.5% (PERF.md, PR 25).

Labels belong to the row index too, drawn from ``label_seed``: which functions
are vulnerable is a property of the data set, not of the run. The real mixes'
constant is one under which the batches whose steps ``correct`` follows hold
no vulnerable function: where ``k`` of a batch's 16 rows are vulnerable and
the untrained model gives every row about ``k / 16``, the rows' gradients
cancel in the batch mean, and a comparison of that mean measures its
conditioning, not the program (PERF.md section 2, PR 25). The configurations
state that property (``check.labels``) and the driver refuses to run without
it.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any

import numpy as np

from harness import spec

TRAFFIC_DIR = Path(__file__).resolve().parent.parent / "traffic"


def load_mix(name: str) -> dict:
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r}: {path} is missing")
    return json.loads(path.read_text())


def sizes(dist: dict, n: int, size_seed: int) -> np.ndarray:
    """``n`` whole sizes from a mixture of ``lognormal`` and ``loguniform``
    parts, clipped to ``[min, max]``. Drawn from ``size_seed`` alone."""
    rng = np.random.default_rng(size_seed)
    parts = dist["parts"]
    shares = np.array([p["share"] for p in parts], np.float64)
    which = rng.choice(len(parts), size=n, p=shares / shares.sum())
    out = np.zeros(n, np.float64)
    for i, p in enumerate(parts):
        if p["dist"] == "lognormal":
            draw = rng.lognormal(np.log(p["median"]), p["sigma"], n)
        elif p["dist"] == "loguniform":
            draw = np.exp(rng.uniform(np.log(p["low"]), np.log(p["high"]), n))
        else:
            raise ValueError(f"unknown size distribution {p['dist']!r}")
        out = np.where(which == i, draw, out)
    return np.clip(out.astype(np.int64), dist["min"], dist["max"])


def labels(params: dict, n: int) -> np.ndarray:
    rng = np.random.default_rng(params["label_seed"])
    return (rng.random(n) < params["positive_rate"]).astype(np.int32)


def generate(name: str, seed: int, overrides: dict[str, Any] | None = None, **kw) -> dict:
    """The inputs of mix ``name`` for ``seed``. ``overrides`` replaces
    parameters of the mix: a joining generator's row count, or a test's tiny
    size."""
    params = {**load_mix(name), **(overrides or {})}
    return spec.load_module("generators", params["generator"]).generate(params, seed, **kw)
