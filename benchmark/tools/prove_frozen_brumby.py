"""``tools/prove_frozen.py`` for the cells whose frozen decoder is
``deepdfa_tpu/llm/brumby.py``: the same sweep (program against reference on
many seeds; the fp8 control and the reference's ``FAULTS`` in the program's
place; ``--step-faults`` / ``--program-faults`` planted in the program), with
the plantings that are *this* decoder's. Same arguments, same output file. This
decoder routes nothing: ``reference/brumby_fusion.py:run`` takes that tool's
``routing`` and hands back none.

Six plantings wrap the retention op or the layer's pieces and so bite on
whichever path runs, the kernel included: ``gate_dropped``, ``pads_in_state``,
``state_not_carried`` (each chunk a row of its own), ``kv_head_mod``,
``qk_norm_skipped``, ``rope_dropped``. Two change the arithmetic inside the
retention and are planted in the plain chunked form, with the kernel taken off
the path: ``degree_1`` and ``normaliser_dropped``. ``count_off`` is a fault of
the ``stats`` path alone.

    python3 benchmark/tools/prove_frozen_brumby.py --workload <name> --seeds 11,12,13 \\
        [--control-seeds 1] [--faults a,b] [--step-faults rope_dropped,count_off] \\
        [--program-faults gate_dropped,kv_head_mod] [--benchmark-file ...]
"""

from __future__ import annotations

import contextlib
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import prove_frozen  # noqa: E402  (puts benchmark/ and the repo on the path)

PLANTABLE = ("degree_1", "gate_dropped", "normaliser_dropped", "pads_in_state",
             "state_not_carried", "kv_head_mod", "qk_norm_skipped", "rope_dropped", "count_off")


def plant(kind: str, setattr_) -> None:
    """Plant ``kind`` in the program's decoder, underneath the driver, through
    ``setattr_(object, name, value)`` (module docstring)."""
    import flax.linen as nn
    import jax.numpy as jnp
    import numpy as np
    from deepdfa_tpu.llm import brumby
    from deepdfa_tpu.ops import power_retention as ops

    real = brumby.power_retention

    class Same(nn.Module):
        def __call__(self, x):
            return x

    def wrap(edit):
        setattr_(brumby, "power_retention", lambda *a, **kw: edit(*a, **kw))

    if kind == "gate_dropped":
        wrap(lambda q, k, v, log_g, mask, **kw: real(q, k, v, log_g * 0.0, mask, **kw))
    elif kind == "pads_in_state":
        wrap(lambda q, k, v, log_g, mask, **kw: real(q, k, v, log_g, jnp.ones_like(mask), **kw))
    elif kind == "state_not_carried":
        def per_chunk(q, k, v, log_g, mask, **kw):
            b, s = mask.shape
            c = kw["chunk"]
            rows = lambda x: x.reshape(b * (s // c), c, *x.shape[2:])
            o = real(rows(q), rows(k), rows(v), rows(log_g), rows(mask), **kw)
            return o.reshape(q.shape)

        wrap(per_chunk)
    elif kind == "kv_head_mod":  # query head h reads key/value head h % kv heads
        def crossed(q, k, v, log_g, mask, **kw):
            b, s, hd = q.shape
            hk = log_g.shape[-1]
            d = k.shape[-1] // hk
            h = hd // d
            order = np.argsort(np.arange(h) % hk, kind="stable")
            o = real(q.reshape(b, s, h, d)[:, :, order].reshape(q.shape), k, v, log_g, mask, **kw)
            return o.reshape(b, s, h, d)[:, :, np.argsort(order)].reshape(q.shape)

        wrap(crossed)
    elif kind == "qk_norm_skipped":
        norm = brumby.RMSNorm
        setattr_(brumby, "RMSNorm", lambda eps, dtype, name: (
            Same(name=name) if name in ("q_norm", "k_norm") else norm(eps, dtype=dtype, name=name)))
    elif kind == "rope_dropped":
        setattr_(brumby, "apply_rope", lambda x, cos, sin: x)
    elif kind in ("degree_1", "normaliser_dropped"):
        setattr_(brumby, "_fused_retention", lambda cfg, s: None)
        if kind == "degree_1":  # phi(q) . phi(k) = q . k
            setattr_(ops, "_weights", lambda scores: scores)
            setattr_(ops, "phi", lambda x: x.astype(jnp.float32))
        else:
            setattr_(ops, "_ratio", lambda num, den, eps: num)
    elif kind == "count_off":
        needed = brumby.chunks_needed
        setattr_(brumby, "chunks_needed", lambda mask, chunk: needed(mask, chunk) + 1)
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
