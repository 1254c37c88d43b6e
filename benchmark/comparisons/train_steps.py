"""Comparison ``train_steps``: the first steps of a training run.

Both sides hand over the same readings: ``loss`` (one a step), ``grad1``
(per-leaf norm of the first gradient as the optimizer gets it) and ``delta``
(per-leaf norm of the parameters' change after the last checked step). Each
number is a gap between the two; the configuration file gives a limit to
those that are compared (``harness/compare.py``), and the others are printed
beside them."""

from __future__ import annotations

import statistics


def _leaf_gaps(prog: dict, ref: dict, leaves) -> dict[str, float]:
    """Each leaf's gap of norms, measured against the reference's norm of that
    leaf or of the median leaf, whichever is larger (some leaves are all but
    zero)."""
    median = statistics.median(ref[n] for n in leaves)
    return {n: abs(prog[n] - ref[n]) / max(ref[n], median, 1e-30) for n in leaves}


def numbers(prog: dict, ref: dict) -> dict[str, float]:
    """The numbers compared, by short name. Raises if the two sides do not
    hold the same leaves."""
    if set(prog["delta"]) != set(ref["delta"]) or set(prog["grad1"]) != set(ref["grad1"]):
        odd = (set(prog["delta"]) ^ set(ref["delta"])) | (set(prog["grad1"]) ^ set(ref["grad1"]))
        raise ValueError(f"program and reference disagree on the leaves: {sorted(odd)[:6]}")
    out = {}
    for i, (p, r) in enumerate(zip(prog["loss"], ref["loss"], strict=True)):
        out[f"loss{i + 1}_gap"] = abs(p - r) / max(abs(r), 1e-30)
    grad = _leaf_gaps(prog["grad1"], ref["grad1"], sorted(ref["grad1"]))
    out["grad1_at"] = max(grad, key=grad.get)
    out["grad1_gap"] = grad[out["grad1_at"]]
    # Leaves whose gradient is nought to rounding in the reference (a key's
    # bias under softmax) move under Adam by round-off alone: left out of the
    # change by a rule on the reference's gradient, not by name. Frozen leaves
    # (no gradient on either side) stay in: they must not move.
    g_median = statistics.median(ref["grad1"].values())
    moved = [n for n in sorted(ref["delta"])
             if n not in ref["grad1"] or ref["grad1"][n] >= 1e-3 * g_median]
    delta = _leaf_gaps(prog["delta"], ref["delta"], moved)
    out["delta_at"] = max(delta, key=delta.get)
    out["delta_gap"] = delta[out["delta_at"]]
    return out
