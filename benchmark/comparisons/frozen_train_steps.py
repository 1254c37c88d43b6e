"""Comparison ``frozen_train_steps``: the first steps of a training run whose
decoder is frozen, so that the trained leaves see it only through the head's
input.

Beside ``train_steps``' numbers over the trained leaves (``loss``, ``grad1``,
``delta``; its rules unchanged), both sides hand over, per checked step, what
reads the decoder directly: ``hidden`` (the final-norm state of every real
token, [n_real, hidden]), ``logits`` and ``routing`` (the experts each token's
router chose, [layers, b, s, k]); the reference adds its own choices before
it took any of the program's (``routing_own``), for every token-layer the
band of its own scores that the experts the two disagree on span (``band``, 0
where they agree: ``reference/longcat_fusion.py``) and the pad mask
(``real``). A program also hands over ``tie``: what ties the pass the check
ran for them to the timed step (``drivers/joint_trainer_frozen.py``).

``hidden_gap``     the worst real token's ``|program - reference| / |reference|``
                   over the state's width: one token through a wrong expert,
                   a wrong gate or a missing part of the layer shows whole
``pooled_gap``     the same over the pooled (last) tokens alone
``logit_gap``      the worst row's gap of the two logits' difference, over
                   the rows' largest
``expert_gap``     over the held experts of every layer (those with eight tokens
                   or more): the mean gap of the tokens the reference sent to
                   that expert over the mean gap of all tokens, less one.
                   Rounding spreads evenly over tokens, so every expert's
                   tokens read the common mean; one expert computed wrongly,
                   skipped or cut short lifts its own tokens above it, by less
                   than the worst token's noise but on all of them
``route_gap``      the share of **all** real token-layers whose choice
                   differs from the reference's own by more than rounding
                   explains: the experts swapped span a band of the
                   reference's scores of ``epsilon`` or more (handed in by the
                   reference). Rounding swaps neighbours at the cut; a wrong
                   router swaps anything
``route_agree_share`` (printed) the share of real token-layers where both
                   chose the same experts; ``route_band_max`` the widest band
                   among those where they did not
``step_logit_gap`` (a program's) the timed step's own logits against those of
                   the check's pass (the step's loss function once more, the
                   states and choices as further outputs), measured as
                   ``logit_gap``. Built alike, the two have given the same
                   bits; two programs that lower the decoder differently part
                   by a bfloat16 ulp at a few elements, which reads up to 9e-4
                   here when one is a pooled token's
``step_count_gap`` (a program's) the widest relative difference between a
                   routing count the timed step left on its span and the same
                   count of the check's pass's choices
"""

from __future__ import annotations

import numpy as np
from harness import spec


def _row_gap(p: np.ndarray, r: np.ndarray) -> np.ndarray:
    p, r = p.astype(np.float64), r.astype(np.float64)
    return np.linalg.norm(p - r, axis=-1) / np.maximum(np.linalg.norm(r, axis=-1), 1e-30)


def _logit_gap(p: np.ndarray, r: np.ndarray) -> float:
    """The worst row's gap of the two logits' difference, over the rows' largest."""
    dp, dr = np.diff(p, axis=-1)[:, 0], np.diff(r, axis=-1)[:, 0]
    return float(np.abs(dp - dr).max() / max(np.abs(dr).max(), 1e-30))


def numbers(prog: dict, ref: dict) -> dict:
    out = spec.load_module("comparisons", "train_steps").numbers(prog, ref)
    worst, at, pooled, logit, means = 0.0, "", 0.0, 0.0, []
    same = unexplained = total = 0
    band_max, excess, excess_at = 0.0, 0.0, ""
    lo, hi = ref["held"]
    for i, (hp, hr, real) in enumerate(zip(prog["hidden"], ref["hidden"], ref["real"],
                                           strict=True)):
        gap = _row_gap(hp, hr)
        means.append(float(gap.mean()))
        j = int(gap.argmax())
        if gap[j] > worst:
            row, pos = np.argwhere(real)[j]
            worst, at = float(gap[j]), f"step {i + 1} row {row} position {pos}"
        used = ref["routing"][i][:, real]  # [layers, n_real, k]
        for layer in range(used.shape[0]):
            for e in range(lo, hi):
                members = (used[layer] == e).any(-1)
                over = (gap[members].mean() / max(gap.mean(), 1e-30) - 1.0
                        if members.sum() >= 8 else 0.0)
                if over > excess:
                    excess = float(over)
                    excess_at = f"step {i + 1} layer {layer} expert {e} ({int(members.sum())} tokens)"
        ends = np.cumsum(real.sum(1)) - 1  # each row's last real token, in the flat order
        pooled = max(pooled, float(gap[ends].max()))
        logit = max(logit, _logit_gap(prog["logits"][i], ref["logits"][i]))
        agree = (np.sort(prog["routing"][i], -1) == np.sort(ref["routing_own"][i], -1)).all(-1)
        band = np.where(real[None], ref["band"][i], 0.0)
        same += int((agree & real[None]).sum())
        total += int(real.sum()) * agree.shape[0]
        unexplained += int((band >= ref["epsilon"]).sum())
        band_max = max(band_max, float(band.max()))
    out.update(hidden_gap=worst, hidden_at=at, hidden_mean_gap=float(np.mean(means)),
               pooled_gap=pooled, logit_gap=logit, expert_gap=excess, expert_at=excess_at,
               route_agree_share=same / max(total, 1), route_gap=unexplained / max(total, 1),
               route_band_max=band_max)
    tie = prog.get("tie")
    if tie is not None:  # a reference playing the program has one forward pass: nothing to tie
        out["step_logit_gap"] = max(
            _logit_gap(step, head) for step, head in zip(prog["logits"], tie["logits"],
                                                         strict=True))
        gap, at = max(
            (abs(step[name] - count) / max(count, 1), f"step {i + 1} {name}")
            for i, (step, counts) in enumerate(zip(tie["step_counts"], tie["counts"],
                                                   strict=True))
            for name, count in counts.items())
        out["step_count_gap"], out["step_count_at"] = gap, at if gap else ""
    return out
