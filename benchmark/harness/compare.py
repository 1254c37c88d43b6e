"""Decide ``correct``: each number compared against a limit of its own.

What the numbers are belongs to the kind of cell: a reference module names
its comparison (``COMPARISON``), found as ``benchmark/comparisons/<name>.py``
with ``numbers(program_readings, reference_readings) -> {name: value}``. A
number ``<x>_gap`` may come with ``<x>_at``, the place where it was widest.
The limits are the configuration file's; a number with no limit there is
printed, not compared."""

from __future__ import annotations

from harness import spec


def numbers(comparison: str, prog: dict, ref: dict) -> dict:
    return spec.load_module("comparisons", comparison).numbers(prog, ref)


def judge(nums: dict, limits: dict[str, float]) -> tuple[bool, dict]:
    """``correct`` and, for the result line, each number beside its limit."""
    shown, ok = {}, True
    for name, limit in limits.items():
        value = nums[name]
        passed = value <= limit
        ok = ok and passed
        shown[name] = {"value": value, "limit": limit}
        at = nums.get(name.replace("_gap", "_at"))
        if at:
            shown[name]["at"] = at
    return ok, shown
