"""``drivers/joint_trainer_frozen.py`` for the third sparse decoder
(``deepdfa_tpu/llm/smallthinker.py``): the same ``JointTrainer.train`` window,
the same ``_check`` (the step's loss function once more), ``tie.logits``,
``tie.counts`` and window counts, as ``joint_trainer_frozen_pangu.py`` does it
— a copy of its own of that module with the two names that say LongCat there
(``model_config``, ``LongcatModel``) bound to this family's. Every layer of
this decoder has a router, so the layers need no renumbering. What this
driver adds:

* **the tie reads the attention's counts too.** ``tie.counts`` gains
  ``moe_gathered`` (every held assignment, where the held range is the whole
  router) and the ``attn`` entry the model sows — ``attn_layers``,
  ``attn_window_layers`` and ``attn_pairs_needed``, reckoned here on the host
  from the check's own pad mask — held against what the timed step left on its
  ``loss.sync`` span (``step_count_gap``);
* **the window's query-key pairs** a global and a window layer need, from the
  rows' real lengths, among the counters ``step_mfu.train``'s FLOP count reads;
* **the checked rows must reach past the window.** With every checked row
  shorter than ``sliding_window_size`` a window layer computes what a global
  layer would and ``correct`` cannot see the window: the configuration states
  ``check.rows: one_longer_than_window`` and the run is refused without one,
  as it is for the labels.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

from deepdfa_tpu.llm.smallthinker import SmallThinkerConfig, SmallThinkerModel
from harness import spec

PUBLISHED = tuple(f.name for f in dataclasses.fields(SmallThinkerConfig))


@dataclasses.dataclass(frozen=True)
class _Config(SmallThinkerConfig):
    """The program's config with the one name the base driver reads and this
    family lacks: the layers that sow a ``routing`` entry (all of them)."""

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers


def model_config(cfg: dict) -> SmallThinkerConfig:
    """The program's config from the file's published keys; ``experts_held``
    says which of the router's experts are here (all of them in the cell)."""
    d = {k: cfg[k] for k in PUBLISHED if k in cfg}
    d["dtype"] = cfg["precision"]["compute_dtype"]
    return _Config.from_hf_dict({**d, **cfg.get("program", {})})


def needed_pairs(mask: np.ndarray, window: int | None) -> int:
    """``llm/smallthinker.py:needed_pairs`` in whole numbers on the host."""
    seen = np.cumsum(mask, axis=-1, dtype=np.int64)
    if window is not None and window < mask.shape[1]:
        seen = seen - np.pad(seen, ((0, 0), (window, 0)))[:, :mask.shape[1]]
    return int(seen[mask].sum())


# uncached: this file's own copy, so that the two names bound below stay LongCat's in the
# copy ``spec.load_module`` hands everyone else
_frozen = spec.load_module.__wrapped__("drivers", "joint_trainer_frozen")
_frozen.model_config = lambda cfg: model_config(cfg)  # late-bound: a test may replace this file's
_frozen.LongcatModel = SmallThinkerModel


class Driver(_frozen.Driver):
    def _checked_rows(self, n: int, indices) -> np.ndarray:
        rows = super()._checked_rows(n, indices)
        want = self.cfg["check"].get("rows")
        if want not in (None, "one_longer_than_window"):
            raise ValueError(f"unknown check.rows {want!r}")
        longest = int(self.data["lengths"][rows[rows >= 0]].max())
        self._longest_checked = longest if n == 0 else max(longest, self._longest_checked)
        window = self.llm_cfg.sliding_window_size
        if (want and n == self.cfg["check"]["steps"] - 1
                and self._longest_checked <= window):
            raise RuntimeError(
                f"the longest checked row holds {self._longest_checked} real tokens, and the "
                f"window is {window}: no checked query reaches past the window, a window layer "
                "computes what a global layer would, and the comparison cannot see the window. "
                "The configuration states check.rows = one_longer_than_window: take a size_seed "
                "(or a shuffle_seed / n_examples) under which a checked row is longer")
        return rows

    def _count(self, index_arrays: list) -> dict:
        """The base's counts, and the query-key pairs a global and a window
        layer need over the same rows (``flops/smallthinker_fusion_train.py``)."""
        out = super()._count(index_arrays)
        if index_arrays:
            idx = np.concatenate([np.asarray(a) for a in jax.device_get(index_arrays)])
            pairs = spec.load_module("flops", self.cfg["flops"]).row_pairs
            lengths = [int(n) for n in self.data["lengths"][idx[idx >= 0]]]
            window = self.llm_cfg.sliding_window_size
            out["attn_pairs_global"] = sum(pairs(n, None) for n in lengths)
            out["attn_pairs_window"] = sum(pairs(n, window) for n in lengths)
        return out

    def _counts_of(self, choice: np.ndarray) -> dict:
        cfg, mask = self.llm_cfg, choice[0, ..., 0] >= 0  # a pad is routed nowhere: -1
        counts = super()._counts_of(choice)
        n_win = cfg.window_layers
        return {
            **counts,
            "moe_gathered": counts["moe_held"] if cfg.holds_every_expert else 0,
            "attn_layers": cfg.num_hidden_layers, "attn_window_layers": n_win,
            "attn_pairs_needed": ((cfg.num_hidden_layers - n_win) * needed_pairs(mask, None)
                                  + n_win * needed_pairs(mask, cfg.sliding_window_size))}

    def _span_counts(self, step: int) -> dict:
        (span,) = self._loss_syncs({step})
        return {k: v for k, v in span.attrs.items() if k.startswith(("moe_", "attn_"))}
