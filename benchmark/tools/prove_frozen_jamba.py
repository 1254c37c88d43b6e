"""``tools/prove_frozen.py`` for the cells whose frozen decoder is
``deepdfa_tpu/llm/jamba.py``: the same sweep (program against reference on
many seeds; the fp8 control and the reference's ``FAULTS`` in the program's
place; ``--step-faults`` / ``--program-faults`` planted in the program), with
the plantings that are *this* decoder's. Same arguments, same output file. This
decoder routes nothing: ``reference/jamba_fusion.py:run`` takes that tool's
``routing`` and hands back none, so a control is simply held against the good
reference.

    python3 benchmark/tools/prove_frozen_jamba.py --workload <name> --seeds 11,12,13 \
        [--control-seeds 2] [--faults a,b] [--step-faults d_skip_skipped,count_off] \
        [--program-faults taps_reversed,state_bf16] [--benchmark-file ...]
"""

from __future__ import annotations

import contextlib
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import prove_frozen  # noqa: E402  (puts benchmark/ and the repo on the path)

PLANTABLE = ("mask_before_conv_skipped", "mask_after_conv_skipped", "state_bf16",
             "d_skip_skipped", "inner_norm_skipped", "attention_as_mamba", "rope_in_attention",
             "taps_reversed", "count_off")


def plant(kind: str, setattr_) -> None:
    """Plant ``kind`` in the program's decoder, underneath the driver, through
    ``setattr_(object, name, value)``. ``attention_as_mamba`` builds the
    attention layers as Mamba layers over the preceding layer's mixer weights
    (planted before the driver loads; not a fault of the step alone);
    ``count_off`` is a fault of the ``stats`` path alone (one attention layer
    counted as a Mamba layer)."""
    import flax.linen as nn
    import jax.numpy as jnp
    from deepdfa_tpu.llm import jamba
    from deepdfa_tpu.ops import selective_scan as ops
    from jax import lax

    conv, scan = jamba.causal_conv1d, jamba.selective_scan
    masked = lambda v, mask: jnp.where(mask[..., None], v, jnp.zeros_like(v))

    class Same(nn.Module):
        def __call__(self, x):
            return x

    if kind == "mask_before_conv_skipped":
        setattr_(jamba, "causal_conv1d", lambda u, w, b, mask: masked(conv(u, w, b), mask))
    elif kind == "mask_after_conv_skipped":  # neither c nor what the state adds is masked
        setattr_(jamba, "causal_conv1d", lambda u, w, b, mask: conv(masked(u, mask), w, b))
        setattr_(jamba, "selective_scan", lambda *args: scan(*args[:6], None))
    elif kind == "taps_reversed":
        setattr_(jamba, "causal_conv1d", lambda u, w, b, mask: conv(u, w[::-1], b, mask))
    elif kind == "state_bf16":
        real = ops._step

        def step(a_t, state, *inp):
            state, _ = real(a_t, state, *inp)
            state = lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)
            return state, jnp.sum(state * inp[-1][:, :, None], axis=1)

        setattr_(ops, "_step", step)
    elif kind == "d_skip_skipped":
        setattr_(jamba, "selective_scan", lambda c, delta, a, b_in, c_in, d, mask: scan(
            c, delta, a, b_in, c_in, d * 0, mask))
    elif kind == "inner_norm_skipped":
        real = jamba.RMSNorm
        setattr_(jamba, "RMSNorm", lambda eps, dtype, name: (
            Same(name=name) if name == "b_norm" else real(eps, dtype=dtype, name=name)))
    elif kind == "rope_in_attention":
        from deepdfa_tpu.llm.llama import rope_cos_sin

        real = jamba.blocked_causal_attention

        def with_rope(q, k, v, **kw):
            d = q.shape[-1]
            cos, sin = rope_cos_sin(jnp.arange(q.shape[1])[None], d, 10000.0)  # [1, s, d/2]
            cos, sin = cos[:, :, None], sin[:, :, None]

            def rot(x):  # rotate-half pairs (i, i + d/2), as the reference's planting
                x1, x2 = x[..., :d // 2].astype(jnp.float32), x[..., d // 2:].astype(jnp.float32)
                return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)

            return real(rot(q), rot(k), v, **kw)

        setattr_(jamba, "blocked_causal_attention", with_rope)
    elif kind == "attention_as_mamba":
        from harness import spec

        real = jamba.JambaLayer
        setattr_(jamba, "JambaLayer", lambda cfg, attention, name: real(cfg, False, name=name))
        driver = spec.load_module("drivers", "joint_trainer_frozen_jamba").Driver
        real_load = driver.load

        def load(self, *args):
            real_load(self, *args)
            p = self.trainer.llm_params
            for i in self.llm_cfg.attention_layers:
                p[f"layers_{i}"]["mamba"] = p[f"layers_{i - 1}"]["mamba"]

        setattr_(driver, "load", load)
    elif kind == "count_off":
        real = jamba.JambaConfig.attention_layers
        setattr_(jamba.JambaConfig, "attention_layers", property(lambda self: real.fget(self)[1:]))
    else:
        raise ValueError(f"{kind!r} is not one of {PLANTABLE}")


@contextlib.contextmanager
def planted(kind: str):
    """``prove_frozen.planted`` over this file's :func:`plant`, bound for the
    block's entry alone: other decoders' tests share the process and that
    module's ``plant``."""
    theirs, prove_frozen.plant = prove_frozen.plant, plant
    try:
        with prove_frozen.planted(kind):
            prove_frozen.plant = theirs
            yield
    finally:
        prove_frozen.plant = theirs


def step_alone(driver, kind: str) -> None:
    """``prove_frozen.step_alone`` with this file's plantings."""
    real, evaluate = driver._real_steps

    def faulty(*args):
        with planted(kind):
            return real(*args)

    driver._real_steps = (faulty, evaluate)


if __name__ == "__main__":
    prove_frozen.plant = plant  # this process sweeps this decoder alone
    sys.exit(prove_frozen.main())
