"""The table of peaks, keyed by the device kind JAX reports. A kind that is
not in the table is an error, never a default."""

from __future__ import annotations

import json
from pathlib import Path


def of(device_kind: str) -> dict:
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add a row to "
                       "benchmark/harness/peaks.json with its source")
    return table[device_kind]
