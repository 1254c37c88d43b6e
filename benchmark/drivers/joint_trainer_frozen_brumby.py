"""``drivers/joint_trainer_frozen_jamba.py`` for the power-retention decoder
(``deepdfa_tpu/llm/brumby.py``): the same ``JointTrainer.train`` window, the
same ``_check`` (the step's loss function once more, the final-norm states and
the encoder's ``stats`` beside ``probs``), ``tie.logits`` and ``tie.counts`` —
a copy of its own of that module with the names that say Jamba there
(``model_config``, ``JambaModel``, ``COUNTS``) bound to this family's. What
this driver adds:

* **the decoder's layers are one scan.** The reference names its leaves per
  layer (``llm/layers_<i>/...``); the program holds each stacked on a leading
  layer axis (``layers/...`` [L, ...]). ``load`` hands the base a view of the
  reference's mapping under the program's names: a stacked leaf is made layer
  by layer, each rounded to the program's dtype before the next is made
  (``g_bias`` stays float32), so a float32 copy of a whole stack never exists;
* **the tie reads the retention's counts** (``retention_layers``, ``fused``,
  ``chunks_needed``, ``chunks_computed``, ``tokens_visited``,
  ``tokens_real``) on the timed step's ``loss.sync`` span against the check's
  pass;
* **the window's visited tokens** — a row's chunks of ``retention.chunk``
  positions that hold a real token, times the chunk — from the rows' real
  lengths, beside the base's counts, for ``retention_bytes``.
"""

from __future__ import annotations

import dataclasses
import re
from collections.abc import Mapping

import jax
import jax.numpy as jnp
import numpy as np

from deepdfa_tpu.llm.brumby import BrumbyConfig, BrumbyModel
from harness import spec

PUBLISHED = tuple(f.name for f in dataclasses.fields(BrumbyConfig))
_LAYER = re.compile(r"^llm/layers_(\d+)/(.*)$")


def model_config(cfg: dict) -> BrumbyConfig:
    """The program's config from the file's published keys."""
    d = {k: cfg[k] for k in PUBLISHED if k in cfg}
    d["dtype"] = cfg["precision"]["compute_dtype"]
    return BrumbyConfig.from_hf_dict({**d, **cfg.get("program", {})})


class Stacked(Mapping):
    """The reference's per-layer leaves under the program's scanned names:
    ``llm/layers/<leaf>`` is ``llm/layers_<i>/<leaf>`` stacked over ``i``,
    each layer rounded to ``dtype`` as it is made (float32 leaves kept)."""

    def __init__(self, weights, dtype, float32_leaves: tuple):
        self.weights, self.dtype, self.float32 = weights, dtype, float32_leaves
        self.stacks: dict[str, list[str]] = {}
        self.names: list[str] = []
        for n in weights:
            m = _LAYER.match(n)
            if m is None:
                self.names.append(n)
                continue
            name = f"llm/layers/{m.group(2)}"
            if name not in self.stacks:
                self.stacks[name] = []
                self.names.append(name)
            self.stacks[name].append(n)

    def __getitem__(self, name: str) -> jax.Array:
        if name not in self.stacks:
            return self.weights[name]
        keep = name.endswith(self.float32)
        return jnp.stack([self.weights[n] if keep else self.weights[n].astype(self.dtype)
                          for n in self.stacks[name]])

    def __iter__(self):
        return iter(self.names)

    def __len__(self):
        return len(self.names)


# uncached: this file's own copy, so that the names bound below stay Jamba's in the copy
# ``spec.load_module`` hands everyone else
_jamba = spec.load_module.__wrapped__("drivers", "joint_trainer_frozen_jamba")
_jamba.model_config = lambda cfg: model_config(cfg)  # late-bound: a test may replace this file's
_jamba.JambaModel = BrumbyModel
_jamba.COUNTS = ("retention_",)


class Driver(_jamba.Driver):
    def load(self, data: dict, weights, seed: int) -> None:
        """The base's ``load`` over the stacked view of the reference's leaves."""
        dtype = jnp.dtype(self.llm_cfg.dtype)
        super().load(data, Stacked(weights, dtype, self.reference.FLOAT32_LEAVES), seed)

    def _count(self, index_arrays: list) -> dict:
        """The base's counts, and the tokens of the chunks that hold a real
        token over the same rows (a left-padded row of ``n`` real tokens in a
        block of whole chunks touches ``ceil(n / chunk)`` of them)."""
        out = super()._count(index_arrays)
        if index_arrays:
            idx = np.concatenate([np.asarray(a) for a in jax.device_get(index_arrays)])
            lengths = self.data["lengths"][idx[idx >= 0]].astype(np.int64)
            chunk = self.llm_cfg.retention_chunk
            out["retention_tokens_visited"] = int((-(-lengths // chunk) * chunk).sum())
        return out
