"""Whether an op runs its Pallas kernel, compiled or interpreted, or its
plain form: the rule every model that holds a kernel asks, once.

A kernel runs compiled where the process has one TPU device, and nowhere
else: more devices may shard batch or heads, and a Pallas call is not
GSPMD-partitionable. The kernel's own module then says whether it takes the
shape (``supports``). Tests steer every model's kernels through
:func:`device_mode` alone: patched to return ``True``, each kernel whose
shape holds runs under the Pallas interpreter.
"""

from __future__ import annotations

import importlib

import jax

__all__ = ["device_mode", "kernel_mode"]


def device_mode() -> bool | None:
    """How this process runs its kernels: ``False`` compiled for its one TPU
    device, ``None`` not at all. Tests patch this to return ``True``, the
    Pallas interpreter."""
    if jax.default_backend() == "tpu" and jax.device_count() == 1:
        return False
    return None


def kernel_mode(module: str, *shape) -> bool | None:
    """The ``interpret`` flag for the kernel of ``deepdfa_tpu.ops.<module>``,
    or ``None`` where the plain form has to run: no kernel here, or a shape
    its ``supports(*shape)`` does not take."""
    mode = device_mode()
    if mode is None:
        return None
    # Pallas costs a second of imports: paid only where a kernel can run
    ops = importlib.import_module(f"deepdfa_tpu.ops.{module}")
    return mode if ops.supports(*shape) else None
