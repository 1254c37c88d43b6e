"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own; this module only joins names to paths
and loads Python files (drivers, references, readers) by file name."""

from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parent.parent
ROOT = BENCH_DIR.parent


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@functools.cache
def load_module(kind: str, name: str) -> ModuleType:
    """``benchmark/<kind>/<name>.py`` as a module (names may hold '-' or '.')."""
    path = BENCH_DIR / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {name!r} under {kind}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(workload: str, bench: dict | None = None) -> dict:
    """The cell ``workload`` with its configuration file read in and the
    metrics it reports resolved: ``{"cell", "config", "end_to_end",
    "per_layer"}``; every metric's entry carries its own data file (``reader``
    and ``args``) from ``end_to_end/`` or ``layer_metrics/``."""
    bench = bench or load_benchmark()
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / entry["file"]).read_text())

    def reports(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    def resolved(kind: str, directory: str) -> list[dict]:
        return [{**m, **json.loads((BENCH_DIR / directory / f"{m['name']}.json").read_text())}
                for m in filter(reports, bench[kind])]

    return {
        "cell": cell,
        "config": config,
        "end_to_end": resolved("end_to_end", "end_to_end"),
        "per_layer": resolved("per_layer", "layer_metrics"),
    }
