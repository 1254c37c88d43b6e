"""``drivers/joint_trainer_frozen.py`` for the decoder of compressed
convolutional attention and top-1 MLP-routed experts (``deepdfa_tpu/llm/
zaya.py``): the same ``JointTrainer.train`` window, the same ``_check`` (the
step's loss function once more), ``tie.logits``, ``tie.counts`` and window
counts, as ``joint_trainer_frozen_smallthinker.py`` does it — a copy of its
own of that module with the two names that say LongCat there
(``model_config``, ``LongcatModel``) bound to this family's. Every layer has a
router, so the layers need no renumbering; the skip is the router's last
option, ``num_experts``, which that module's ``_counts_of`` already counts as
``moe_zero``. What this driver adds:

* **the router stays float32.** The base rounds every decoder leaf to the
  program's dtype but those it knows as a router's by name; ``load`` puts this
  router's leaves (``reference.FLOAT32_LEAVES``) back as the reference made
  them;
* **the tie reads the attention's counts too**: ``moe_gathered``,
  ``cca_layers`` and ``attn_pairs_needed`` (reckoned here on the host from the
  check's own pad mask) against what the timed step left on its
  ``loss.sync`` span (``step_count_gap``);
* **the window's attention work** among the counters: the query-key pairs a
  layer needs (``attn_pairs_global``, the rows' causal pairs) and the
  positions the attention kernel visits (``attn_tokens_visited``: a
  left-padded row of ``n`` real tokens holds ``ceil(n / tile)`` tiles that
  hold a real token), for ``flops/zaya_fusion_train.py``.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
from flax.traverse_util import flatten_dict, unflatten_dict

from deepdfa_tpu.llm.zaya import ZayaConfig, ZayaModel
from harness import spec

PUBLISHED = (*(f.name for f in dataclasses.fields(ZayaConfig)), "rope_parameters")


@dataclasses.dataclass(frozen=True)
class _Config(ZayaConfig):
    """The program's config with the one name the base driver reads and this
    family lacks: the layers that sow a ``routing`` entry (all of them)."""

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers


def model_config(cfg: dict) -> ZayaConfig:
    """The program's config from the file's published keys; ``experts_held``
    says which of the router's experts are here (all of them in the cell)."""
    d = {k: cfg[k] for k in PUBLISHED if k in cfg}
    d["dtype"] = cfg["precision"]["compute_dtype"]
    return _Config.from_hf_dict({**d, **cfg.get("program", {})})


# uncached: this file's own copy, so that the two names bound below stay LongCat's in the
# copy ``spec.load_module`` hands everyone else
_frozen = spec.load_module.__wrapped__("drivers", "joint_trainer_frozen")
_frozen.model_config = lambda cfg: model_config(cfg)  # late-bound: a test may replace this file's
_frozen.LongcatModel = ZayaModel
_smallthinker = spec.load_module("drivers", "joint_trainer_frozen_smallthinker")


class Driver(_frozen.Driver):
    def load(self, data: dict, weights, seed: int) -> None:
        """The base's ``load``, then the router's leaves float32 again."""
        super().load(data, weights, seed)
        tr, keep = self.trainer, self.reference.FLOAT32_LEAVES
        flat = flatten_dict(tr.llm_params, sep="/")
        tr.llm_params = unflatten_dict(
            {n: weights[f"llm/{n}"] if f"/{n}".endswith(keep) else v for n, v in flat.items()},
            sep="/")

    def _count(self, index_arrays: list) -> dict:
        """The base's counts, the causal pairs a layer needs over the same
        rows and the positions the attention kernel visits for them."""
        out = super()._count(index_arrays)
        if index_arrays:
            from deepdfa_tpu.ops import gqa_attention

            idx = np.concatenate([np.asarray(a) for a in jax.device_get(index_arrays)])
            lengths = self.data["lengths"][idx[idx >= 0]].astype(np.int64)
            out["attn_pairs_global"] = int((lengths * (lengths + 1) // 2).sum())
            cfg, block = self.llm_cfg, self.jcfg.block_size
            if gqa_attention.supports(block, cfg.num_attention_heads, cfg.num_key_value_heads,
                                      cfg.head_dim):  # a block the kernel takes
                tile = gqa_attention.default_tile(block)
                out["attn_tokens_visited"] = int((-(-lengths // tile) * tile).sum())
        return out

    def _counts_of(self, choice: np.ndarray) -> dict:
        cfg, mask = self.llm_cfg, choice[0, ..., 0] >= 0  # a pad is routed nowhere: -1
        counts = super()._counts_of(choice)
        return {
            **counts,
            "moe_gathered": counts["moe_held"] if cfg.holds_every_expert else 0,
            "cca_layers": cfg.num_hidden_layers,
            "attn_pairs_needed": cfg.num_hidden_layers * _smallthinker.needed_pairs(mask, None)}

    def _span_counts(self, step: int) -> dict:
        (span,) = self._loss_syncs({step})
        return {k: v for k, v in span.attrs.items() if k.startswith(("moe_", "cca_", "attn_"))}
