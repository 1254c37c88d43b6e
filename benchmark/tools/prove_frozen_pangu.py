"""``tools/prove_frozen.py`` for the cells whose frozen decoder is
``deepdfa_tpu/llm/pangu_moe.py``: the same sweep (program against reference on
many seeds; the fp8 control and the reference's ``FAULTS`` in the program's
place; ``--step-faults`` / ``--program-faults`` planted in the program), with
the plantings that are *this* decoder's. Same arguments, same output file.

    python3 benchmark/tools/prove_frozen_pangu.py --workload <name> --seeds 11,12,13 \
        [--control-seeds 2] [--faults a,b] [--step-faults expert_skipped] \
        [--program-faults shared_skipped,softmax_scores]
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import prove_frozen  # noqa: E402  (puts benchmark/ and the repo on the path)

PLANTABLE = ("shared_skipped", "not_renormalised", "scaling_one", "softmax_scores",
             "post_norm_skipped", "dense_as_experts", "expert_skipped", "count_off")


def plant(kind: str, setattr_) -> None:
    """Plant ``kind`` in the program's decoder, underneath the driver, through
    ``setattr_(object, name, value)``. ``dense_as_experts`` builds the leading
    layer as an expert layer over the next layer's expert weights (planted
    before the driver loads; not a fault of the step alone); ``count_off`` is
    a fault of the ``stats`` path alone."""
    import dataclasses

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from deepdfa_tpu.llm import longcat, pangu_moe
    from jax import lax

    real_route, real_held = pangu_moe.route, longcat.held_expert_ffn

    class Nothing(nn.Module):
        def __call__(self, x):
            return jnp.zeros_like(x)

    class Same(nn.Module):
        def __call__(self, x):
            return x

    def softmax_route(x, w_r, cfg):
        logits = jnp.dot(x.astype(jnp.float32), w_r, precision=lax.Precision.HIGHEST)
        top, choice = lax.top_k(jax.nn.softmax(logits, -1), cfg.num_experts_per_tok)
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
        return choice.astype(jnp.int32), cfg.routed_scaling_factor * top

    if kind == "shared_skipped":
        real = pangu_moe.DenseFFN
        setattr_(pangu_moe, "DenseFFN", lambda cfg, width, name: (
            Nothing(name=name) if name == "shared_expert" else real(cfg, width, name=name)))
    elif kind == "not_renormalised":
        setattr_(pangu_moe, "route", lambda x, w_r, cfg: real_route(
            x, w_r, dataclasses.replace(cfg, norm_topk_prob=False)))
    elif kind == "scaling_one":
        setattr_(pangu_moe, "route", lambda x, w_r, cfg: real_route(
            x, w_r, dataclasses.replace(cfg, routed_scaling_factor=1.0)))
    elif kind == "softmax_scores":
        setattr_(pangu_moe, "route", softmax_route)
    elif kind == "post_norm_skipped":
        real = pangu_moe.RMSNorm
        setattr_(pangu_moe, "RMSNorm", lambda eps, dtype, name: (
            Same(name=name) if name == "post_attn_norm" else real(eps, dtype=dtype, name=name)))
    elif kind == "dense_as_experts":
        from harness import spec

        real = pangu_moe.PanguMoeLayer
        setattr_(pangu_moe, "PanguMoeLayer", lambda cfg, dense, name: real(cfg, False, name=name))
        driver = spec.load_module("drivers", "joint_trainer_frozen_pangu").Driver
        real_load = driver.load

        def load(self, *args):
            real_load(self, *args)
            p, first = self.trainer.llm_params, self.llm_cfg.first_k_dense_replace
            for i in range(first):
                p[f"layers_{i}"]["moe"] = p[f"layers_{first}"]["moe"]

        setattr_(driver, "load", load)
    elif kind == "expert_skipped":
        setattr_(longcat, "held_expert_ffn", lambda u, choice, gates, *w, lo, rows: real_held(
            u, jnp.where(choice == lo + w[0].shape[0] // 2, -1, choice), gates, *w,
            lo=lo, rows=rows))
    elif kind == "count_off":
        real_call = pangu_moe.ExpertLayer.__call__

        def call(self, u, token_mask):
            out, counts = real_call(self, u, token_mask)
            return out, {**counts, "held": counts["held"] + 1}
        setattr_(pangu_moe.ExpertLayer, "__call__", call)
    else:
        raise ValueError(f"{kind!r} is not one of {PLANTABLE}")


prove_frozen.plant = plant  # what its ``planted`` and ``step_alone`` plant

if __name__ == "__main__":
    sys.exit(prove_frozen.main())
