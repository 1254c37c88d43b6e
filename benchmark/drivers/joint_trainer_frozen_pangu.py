"""``drivers/joint_trainer_frozen.py`` for the other sparse decoder
(``deepdfa_tpu/llm/pangu_moe.py``): the same ``JointTrainer.train`` window,
the same ``_check`` (the step's loss function once more), ``tie.logits``,
``tie.counts`` and window counts. Only what names LongCat there is replaced:

* the config and module classes — that file builds both by name inside
  ``__init__``, so this one loads **a copy of its own** of that module and
  binds the two names (``model_config``, ``LongcatModel``) to this family's;
* which layers have a ``routing`` entry — that file's ``run`` reads
  ``layers_0 .. layers_{num_layers - 1}``; here a leading dense layer has no
  router, so the config handed to it counts the expert layers under that name
  and ``_check``'s choices are renumbered to match.

The float32 router leaf goes by the name that file already looks for
(``router_kernel``), and its ``_counts_of`` reads a decoder with no
zero-compute experts as it stands (no choice is ``>= n_routed_experts``, so
``moe_zero`` is 0 on both sides of the tie).
"""

from __future__ import annotations

import dataclasses

from deepdfa_tpu.llm.pangu_moe import PanguMoeConfig, PanguMoeModel
from harness import spec

PUBLISHED = tuple(f.name for f in dataclasses.fields(PanguMoeConfig))


@dataclasses.dataclass(frozen=True)
class _Config(PanguMoeConfig):
    """The program's config with the one name the base driver reads and this
    family lacks: the layers that sow a ``routing`` entry."""

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace


def model_config(cfg: dict) -> PanguMoeConfig:
    """The program's config from the file's published keys: the router keeps
    its published width, ``experts_held`` says which experts are here."""
    d = {k: cfg[k] for k in PUBLISHED if k in cfg}
    d["n_routed_experts"] = cfg["published"]["n_routed_experts"]
    d["dtype"] = cfg["precision"]["compute_dtype"]
    return _Config.from_hf_dict({**d, **cfg.get("program", {})})


# uncached: this file's own copy, so that the two names bound below stay LongCat's in the
# copy ``spec.load_module`` hands everyone else
_frozen = spec.load_module.__wrapped__("drivers", "joint_trainer_frozen")
_frozen.model_config = lambda cfg: model_config(cfg)  # late-bound: a test may replace this file's
_frozen.LongcatModel = PanguMoeModel


class Driver(_frozen.Driver):
    def __init__(self, cfg: dict, reference):
        super().__init__(cfg, reference)
        check, first = self._check, self.llm_cfg.first_k_dense_replace

        def renumbered(params, llm_params, jb):
            (probs, hidden, routing), norm = check(params, llm_params, jb)
            return (probs, hidden, {f"layers_{i}": routing[f"layers_{first + i}"]
                                    for i in range(self.llm_cfg.num_layers)}), norm

        self._check = renumbered
