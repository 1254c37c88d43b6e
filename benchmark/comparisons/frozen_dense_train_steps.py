"""Comparison ``frozen_dense_train_steps``: ``frozen_train_steps`` for a frozen
decoder that routes nothing — the first steps of a training run whose trained
leaves see the decoder only through the head's input.

Beside ``train_steps``' numbers over the trained leaves (``loss``, ``grad1``,
``delta``; its rules unchanged), both sides hand over, per checked step,
``hidden`` (the final-norm state of every real token, [n_real, hidden]) and
``logits``; the reference adds the pad mask (``real``). A program also hands
over ``tie``: what ties the pass the check ran for the states to the timed
step (``drivers/joint_trainer_frozen_jamba.py``).

``hidden_gap``     the worst real token's ``|program - reference| / |reference|``
                   over the state's width: a state that integrated a pad, a
                   wrong tap, a missing term of the mixer shows whole at the
                   tokens it reaches
``hidden_mean_gap`` (printed) the mean token's
``pooled_gap``     (printed) the same over the pooled (last) tokens alone
``logit_gap``      (printed) the worst row's gap of the two logits'
                   difference, over the rows' largest
``step_logit_gap`` (a program's) the timed step's own logits against those of
                   the check's pass (the step's loss function once more, the
                   states as a further output), measured as ``logit_gap``
``step_count_gap`` (a program's) the widest relative difference between a
                   count the timed step's encoder left on its ``loss.sync``
                   span (``ssm_layers``, ``ssm_fused``, ``attn_layers``,
                   ``attn_fused``) and the same count of the check's pass
"""

from __future__ import annotations

import numpy as np
from harness import spec

_frozen = spec.load_module("comparisons", "frozen_train_steps")
_row_gap, _logit_gap = _frozen._row_gap, _frozen._logit_gap


def numbers(prog: dict, ref: dict) -> dict:
    out = spec.load_module("comparisons", "train_steps").numbers(prog, ref)
    worst, at, pooled, logit, means = 0.0, "", 0.0, 0.0, []
    for i, (hp, hr, real) in enumerate(zip(prog["hidden"], ref["hidden"], ref["real"],
                                           strict=True)):
        gap = _row_gap(hp, hr)
        means.append(float(gap.mean()))
        j = int(gap.argmax())
        if gap[j] > worst:
            row, pos = np.argwhere(real)[j]
            worst, at = float(gap[j]), f"step {i + 1} row {row} position {pos}"
        ends = np.cumsum(real.sum(1)) - 1  # each row's last real token, in the flat order
        pooled = max(pooled, float(gap[ends].max()))
        logit = max(logit, _logit_gap(prog["logits"][i], ref["logits"][i]))
    out.update(hidden_gap=worst, hidden_at=at, hidden_mean_gap=float(np.mean(means)),
               pooled_gap=pooled, logit_gap=logit)
    tie = prog.get("tie")
    if tie is not None:  # a reference playing the program has one forward pass: nothing to tie
        out["step_logit_gap"] = max(
            _logit_gap(step, head) for step, head in zip(prog["logits"], tie["logits"],
                                                         strict=True))
        gap, at = max(
            (abs(step.get(name, -1) - count) / max(count, 1), f"step {i + 1} {name}")
            for i, (step, counts) in enumerate(zip(tie["step_counts"], tie["counts"],
                                                   strict=True))
            for name, count in counts.items())
        out["step_count_gap"], out["step_count_at"] = gap, at if gap else ""
    return out
