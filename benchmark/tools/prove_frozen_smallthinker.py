"""``tools/prove_frozen.py`` for the cells whose frozen decoder is
``deepdfa_tpu/llm/smallthinker.py``: the same sweep (program against reference
on many seeds; the fp8 control and the reference's ``FAULTS`` in the program's
place; ``--step-faults`` / ``--program-faults`` planted in the program), with
the plantings that are *this* decoder's. Same arguments, same output file.

    python3 benchmark/tools/prove_frozen_smallthinker.py --workload <name> --seeds 11,12,13 \
        [--control-seeds 2] [--faults a,b] [--step-faults expert_skipped] \
        [--program-faults window_dropped,router_reads_m]
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import prove_frozen  # noqa: E402  (puts benchmark/ and the repo on the path)

PLANTABLE = ("window_dropped", "rope_on_global", "rope_dropped", "router_reads_m",
             "silu_for_relu", "sigmoid_gates", "softmax_all", "expert_skipped", "count_off")


def plant(kind: str, setattr_) -> None:
    """Plant ``kind`` in the program's decoder, underneath the driver, through
    ``setattr_(object, name, value)``. ``count_off`` is a fault of the
    ``stats`` path alone."""
    import jax
    import jax.numpy as jnp
    from deepdfa_tpu.llm import longcat, smallthinker
    from jax import lax

    real_layer, real_held = smallthinker.SmallThinkerLayer, longcat.held_expert_ffn
    real_call = smallthinker.ExpertLayer.__call__

    def gates_by(rule):
        def route(n, w_r, cfg):
            logits = jnp.dot(n.astype(jnp.float32), w_r, precision=lax.Precision.HIGHEST)
            top, choice = lax.top_k(logits, cfg.moe_num_active_primary_experts)
            return choice.astype(jnp.int32), rule(logits, top, choice)
        return route

    def layer_with(**other):  # a layer built as another kind: ``rope=`` / ``window=`` replaced
        def build(cfg, rope, window, name):
            kind = {"rope": rope, "window": window, **other}
            return real_layer(cfg, kind["rope"], kind["window"], name=name)
        return build

    if kind in ("window_dropped", "rope_on_global", "rope_dropped"):
        setattr_(smallthinker, "SmallThinkerLayer", layer_with(**{
            "window_dropped": {"window": None}, "rope_on_global": {"rope": True},
            "rope_dropped": {"rope": False}}[kind]))
    elif kind == "router_reads_m":
        setattr_(smallthinker.ExpertLayer, "__call__",
                 lambda self, n, m, token_mask: real_call(self, m, m, token_mask))
    elif kind == "silu_for_relu":
        real = smallthinker.held_experts
        setattr_(smallthinker, "held_experts", lambda *a, activation: real(
            *a, activation=jax.nn.silu))
    elif kind == "sigmoid_gates":
        setattr_(smallthinker, "route", gates_by(
            lambda logits, top, choice: jax.nn.sigmoid(top) / jnp.sum(
                jax.nn.sigmoid(top), -1, keepdims=True)))
    elif kind == "softmax_all":
        setattr_(smallthinker, "route", gates_by(
            lambda logits, top, choice: jnp.take_along_axis(
                jax.nn.softmax(logits, -1), choice, axis=-1)))
    elif kind == "expert_skipped":
        setattr_(longcat, "held_expert_ffn", lambda u, choice, gates, *w, lo, **kw: real_held(
            u, jnp.where(choice == lo + w[0].shape[0] // 2, -1, choice), gates, *w, lo=lo, **kw))
    elif kind == "count_off":
        def call(self, n, m, token_mask):
            out, counts = real_call(self, n, m, token_mask)
            return out, {**counts, "held": counts["held"] + 1}
        setattr_(smallthinker.ExpertLayer, "__call__", call)
    else:
        raise ValueError(f"{kind!r} is not one of {PLANTABLE}")


prove_frozen.plant = plant  # what its ``planted`` and ``step_alone`` plant

if __name__ == "__main__":
    sys.exit(prove_frozen.main())
