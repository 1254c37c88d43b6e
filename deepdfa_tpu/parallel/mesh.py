"""Device-mesh construction.

Axes (fixed names across the framework):

- ``dp``   — data parallel (batch-sharded; grads psum over ICI)
- ``fsdp`` — fully-sharded data parallel (params sharded, gathered per layer)
- ``tp``   — tensor parallel (matmul-sharded)
- ``sp``   — sequence/context parallel (ring attention for long functions)

Replaces: Lightning DDP/NCCL process groups (``config_default.yaml:3``),
``torch.nn.DataParallel`` (``MSIVD/msivd/train.py:936``) and HF accelerate
``device_map`` placement (``train.py:883``) — one mesh, shardings annotated,
XLA inserts the collectives.
"""

from __future__ import annotations

import logging

import numpy as np
import jax
from jax.sharding import Mesh

from deepdfa_tpu.config import MeshConfig
from deepdfa_tpu.resilience import faults

AXES = ("dp", "fsdp", "tp", "sp")

__all__ = ["AXES", "build_mesh", "local_mesh", "initialize_multihost", "probed_devices"]

logger = logging.getLogger(__name__)


def build_mesh(cfg: MeshConfig, devices: list | None = None) -> Mesh:
    """Build a named mesh over ``devices`` (default: all).

    Device order follows ``jax.devices()``; on real slices that order is
    ICI-contiguous, so the fastest-varying axes (tp, sp) land on neighbouring
    chips and dp spans the slower links — collectives ride ICI, DCN only
    crosses hosts on the leading axis.

    The ``mesh.device_lost`` fault point halves the visible device list —
    the lost-host scenario: the surviving slice builds a smaller mesh (a
    ``dp=-1`` config absorbs the shrink) and the elastic resume path
    (:mod:`deepdfa_tpu.parallel.elastic`) carries the run across.
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    if faults.fire("mesh.device_lost"):
        survivors = max(1, len(devices) // 2)
        logger.warning(
            "injected mesh.device_lost: %d of %d devices survive",
            survivors, len(devices),
        )
        devices = devices[:survivors]
    sizes = cfg.axis_sizes(len(devices))
    shape = tuple(sizes[a] for a in AXES)
    dev_array = np.array(devices).reshape(shape)
    return Mesh(dev_array, AXES)


def local_mesh(n_devices: int | None = None, **axis_sizes: int) -> Mesh:
    """Convenience mesh over the first ``n_devices`` local devices, e.g.
    ``local_mesh(8, tp=4)``. Unnamed axes default to 1, except ``dp`` which
    absorbs the remaining devices when not given explicitly."""
    available = jax.devices()
    if n_devices is not None and n_devices > len(available):
        raise ValueError(f"requested {n_devices} devices, only {len(available)} available")
    devices = available[: n_devices or len(available)]
    sizes = {a: axis_sizes.get(a, 1) for a in AXES}
    if "dp" not in axis_sizes:
        sizes["dp"] = -1
    return build_mesh(MeshConfig(**sizes), devices)


def probed_devices(deadline_s: float, on_timeout=None) -> list:
    """Device init behind the hung-collective watchdog: the first
    ``jax.devices()`` touch initialises the backend, a host-blocking call
    with no timeout of its own. Raises
    :class:`~deepdfa_tpu.resilience.watchdog.WatchdogTimeout` after
    ``deadline_s`` instead — callers journal and abort cleanly."""
    from deepdfa_tpu.resilience.watchdog import HangWatchdog

    return HangWatchdog(deadline_s, on_timeout=on_timeout).call(
        "device_init", jax.devices
    )


def initialize_multihost(coordinator: str | None = None, num_processes: int | None = None,
                         process_id: int | None = None) -> None:
    """Multi-host bring-up over DCN (``jax.distributed.initialize``).

    With no arguments, defers to JAX's pod auto-detection (TPU metadata /
    cluster env); pass ``num_processes=1`` to explicitly skip. The reference
    had no multi-node training path at all (SURVEY.md §2.3); this is the
    pod-scale entry point.
    """
    if num_processes == 1:
        return
    if coordinator is None and num_processes is None and process_id is None:
        jax.distributed.initialize()
        return
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
