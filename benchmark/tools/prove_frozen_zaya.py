"""``tools/prove_frozen.py`` for the cells whose frozen decoder is
``deepdfa_tpu/llm/zaya.py``: the same sweep (program against reference on
many seeds; the fp8 control and the reference's ``FAULTS`` in the program's
place; ``--step-faults`` / ``--program-faults`` planted in the program), with
the plantings that are *this* decoder's. Same arguments, same output file.

    python3 benchmark/tools/prove_frozen_zaya.py --workload <name> --seeds 11,12,13 \
        [--control-seeds 2] [--faults a,b] [--step-faults expert_skipped] \
        [--program-faults value_shift_dropped,eda_dropped]
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import prove_frozen  # noqa: E402  (puts benchmark/ and the repo on the path)

PLANTABLE = ("value_shift_dropped", "qk_mean_dropped", "depthwise_conv_dropped",
             "grouped_conv_dropped", "temperature_dropped", "rope_whole_head", "eda_dropped",
             "skip_never_taken", "expert_skipped", "count_off")


def plant(kind: str, setattr_) -> None:
    """Plant ``kind`` in the program's decoder, underneath the driver, through
    ``setattr_(object, name, value)``: the CCA prologue's steps
    (``ops/cca.py``), the router's (``llm/zaya.py``) or the held experts'
    (``longcat.held_expert_ffn``). ``count_off`` is a fault of the ``stats``
    path alone."""
    import jax.numpy as jnp
    from deepdfa_tpu.llm import longcat, zaya
    from deepdfa_tpu.ops import cca

    real_held, real_call = longcat.held_expert_ffn, zaya.ZayaExperts.__call__
    real_l2, real_route = cca.l2_temperature, zaya.route
    # what a dropped step hands on: its input, the pads zeroed as the step would have
    unchanged = lambda x, mask: jnp.where(mask.reshape(mask.shape + (1,) * (x.ndim - 2)), x, 0)

    if kind == "value_shift_dropped":
        setattr_(cca, "value_shift", unchanged)
    elif kind == "qk_mean_dropped":
        setattr_(cca, "qk_mean", lambda q2, k2, q0, k0: (q2, k2))
    elif kind == "depthwise_conv_dropped":
        setattr_(cca, "depthwise_conv", lambda u, w, mask: unchanged(u, mask))
    elif kind == "grouped_conv_dropped":
        setattr_(cca, "grouped_conv", lambda u, w, mask, dtype: unchanged(u, mask))
    elif kind == "temperature_dropped":
        setattr_(cca, "l2_temperature", lambda q, k, tau: real_l2(q, k, jnp.ones_like(tau)))
    elif kind == "rope_whole_head":
        setattr_(zaya.ZayaConfig, "rotary_dim", property(lambda cfg: cfg.head_dim))
    elif kind == "eda_dropped":
        setattr_(zaya, "eda", lambda r, prev, gamma: r)
    elif kind == "skip_never_taken":
        setattr_(zaya, "route", lambda logits, bias: real_route(logits, bias.at[-1].set(-jnp.inf)))
    elif kind == "expert_skipped":
        setattr_(longcat, "held_expert_ffn", lambda u, choice, gates, *w, lo, **kw: real_held(
            u, jnp.where(choice == lo + w[0].shape[0] // 2, -1, choice), gates, *w, lo=lo, **kw))
    elif kind == "count_off":
        def call(self, m, choice, gate):
            out, counts = real_call(self, m, choice, gate)
            return out, {**counts, "held": counts["held"] + 1}
        setattr_(zaya.ZayaExperts, "__call__", call)
    else:
        raise ValueError(f"{kind!r} is not one of {PLANTABLE}")


prove_frozen.plant = plant  # what its ``planted`` and ``step_alone`` plant

if __name__ == "__main__":
    sys.exit(prove_frozen.main())
