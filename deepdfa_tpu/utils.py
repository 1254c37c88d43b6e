"""Storage layout, hashing, host-side parallel map, deterministic seeding.

Reference surface covered: ``DDFA/sastvd/__init__.py:37-250`` (storage_dir /
external_dir / processed_dir / cache_dir, get_run_id, hashstr, dfmp) minus the
Singularity wrapper, which has no TPU-era role.
"""

from __future__ import annotations

import datetime
import hashlib
import multiprocessing
import os
import random
from pathlib import Path
from typing import Any, Callable, Iterable, Sequence

import numpy as np

__all__ = [
    "project_dir",
    "storage_dir",
    "external_dir",
    "interim_dir",
    "processed_dir",
    "cache_dir",
    "get_dir",
    "get_run_id",
    "hashstr",
    "dfmp",
    "chunks",
    "seed_all",
    "debug_nans",
    "setup_compile_cache",
    "describe_backend",
    "require_backend",
    "BackendError",
]


def project_dir() -> Path:
    """Repo root (directory containing the ``deepdfa_tpu`` package)."""
    return Path(__file__).resolve().parent.parent


def storage_dir() -> Path:
    """Storage root; override with env ``DEEPDFA_STORAGE``.

    Mirrors the reference's ``storage_dir()`` + ``SINGSTORAGE`` override
    (``sastvd/__init__.py:42-58``).
    """
    override = os.environ.get("DEEPDFA_STORAGE")
    path = Path(override) if override else project_dir() / "storage"
    path.mkdir(exist_ok=True, parents=True)
    return path


def _sub(name: str) -> Path:
    path = storage_dir() / name
    path.mkdir(exist_ok=True, parents=True)
    return path


def external_dir() -> Path:
    """Downloaded / externally produced artifacts (raw CSVs, Joern outputs)."""
    return _sub("external")


def interim_dir() -> Path:
    """Intermediate artifacts."""
    return _sub("interim")


def processed_dir() -> Path:
    """Fully processed, training-ready artifacts."""
    return _sub("processed")


def cache_dir() -> Path:
    """Memoisation caches; safe to delete."""
    return _sub("cache")


def get_dir(path: Path | str) -> Path:
    """mkdir -p and return. ``exist_ok`` makes this safe under concurrency
    (the reference documents the same rationale, ``sastvd/__init__.py:26-34``)."""
    path = Path(path)
    path.mkdir(exist_ok=True, parents=True)
    return path


def get_run_id(args: Sequence[str] | None = None) -> str:
    """Timestamped unique run id, e.g. ``202607290755_1a2b3c_msg``.

    Parity with ``sastvd/__init__.py:85-103`` (timestamp + short random hex +
    optional slug), reproducible when ``seed_all`` was called.
    """
    stamp = datetime.datetime.now().strftime("%Y%m%d%H%M%S")
    nonce = "%06x" % random.randrange(16**6)
    slug = "_".join(str(a) for a in args) if args else ""
    return f"{stamp}_{nonce}" + (f"_{slug}" if slug else "")


def hashstr(s: str) -> int:
    """Stable small-int hash of a string: sha1 mod 1e8.

    Same construction as the reference (``sastvd/__init__.py:188-192``) so
    hash-derived artifacts are comparable across frameworks.
    """
    return int(hashlib.sha1(s.encode("utf-8")).hexdigest(), 16) % (10**8)


def chunks(seq: Sequence[Any], n: int) -> Iterable[Sequence[Any]]:
    """Yield successive n-sized chunks."""
    for i in range(0, len(seq), n):
        yield seq[i : i + n]


def dfmp(
    df,
    function: Callable[[Any], Any],
    columns: str | Sequence[str] | None = None,
    ordr: bool = True,
    workers: int = 6,
    cs: int = 10,
    desc: str = "Run: ",
) -> list:
    """Parallel map over a DataFrame's records (host-side CPU fan-out).

    Parity with ``sastvd/__init__.py:195-244``: items are full records
    (dicts), a single column's values, or tuples of the selected columns;
    ordered (``imap``) or unordered (``imap_unordered``); chunked; tqdm'd.
    Falls back to a serial map when ``workers <= 1`` (useful in tests and on
    single-core hosts).

    Workers come from an explicit **spawn** context: the default fork start
    method after a jax import can deadlock children on inherited runtime
    locks, and ``maxtasksperchild`` recycles workers so one leaky native
    extraction cannot grow a worker process unboundedly. A worker exception
    propagates to the caller (the pool survives and is torn down cleanly).
    """
    import tqdm

    if columns is None:
        items = df.to_dict("records")
    elif isinstance(columns, str):
        items = df[columns].tolist()
    else:
        items = list(df[list(columns)].itertuples(index=False, name=None))

    if workers <= 1:
        return [function(i) for i in tqdm.tqdm(items, total=len(items), desc=desc)]

    mapper = lambda pool: pool.imap(function, items, cs) if ordr else pool.imap_unordered(function, items, cs)
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(processes=workers, maxtasksperchild=64) as pool:
        return list(tqdm.tqdm(mapper(pool), total=len(items), desc=desc))


def seed_all(seed: int = 0) -> None:
    """Seed every host-side RNG we use (random, numpy).

    JAX randomness is functional (explicit ``jax.random.key``); training code
    derives keys from the config seed, so this only needs to cover host RNGs.
    Parity with ``code_gnn/globals.py:26-33``.
    """
    random.seed(seed)
    np.random.seed(seed)


def debug_nans(enable: bool = True) -> None:
    """TPU-era analogue of the reference trainer's ``detect_anomaly: true``
    (``configs/config_default.yaml:41``): make XLA error out on NaNs."""
    import jax

    jax.config.update("jax_debug_nans", enable)


def setup_compile_cache() -> Path:
    """Place JAX's persistent compilation cache; every entry point calls
    this first, before anything compiles. Returns the cache directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the program sets no
    directory in code — JAX reads the variable itself, so an operator (or
    the chip tool) moves the cache by exporting it. Where it is not, the
    cache goes to ONE fixed path inside the checkout, ``<project_dir>/
    .jax_cache`` (git-ignored): the path is part of the cache key, so a
    directory made from a pid, a run id, ``tempfile`` or the clock would
    never hit. The minimum-compile-time threshold drops to zero — JAX's
    default (1 s) would skip the small GGNN programs (serve buckets, the
    train step), which are exactly what a cold start recompiles."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        path = Path(env)
    else:
        path = project_dir() / ".jax_cache"
        jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class BackendError(RuntimeError):
    """The process initialised a backend other than the one it was given."""


def describe_backend() -> dict:
    """The device as JAX reports it — what every entry point states at
    start (JAX drops to CPU with only a warning when libtpu fails to
    initialise, so a run that does not say where it ran proves nothing)."""
    import jax

    devices = jax.devices()
    return {
        "backend": jax.default_backend(),
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def require_backend() -> dict:
    """:func:`describe_backend`, refusing a backend the caller did not ask
    for. The accelerator is the default: with ``JAX_PLATFORMS`` unset (or
    empty) anything but ``tpu`` raises :class:`BackendError`. A caller that
    pins a platform (``JAX_PLATFORMS=cpu`` for tests and rehearsals) gets
    exactly that platform, labelled as such — never a silent substitute."""
    import jax

    info = describe_backend()
    asked = (jax.config.jax_platforms or "").split(",")[0].strip().lower()
    want = asked or "tpu"
    if info["backend"] != want:
        raise BackendError(
            f"backend is {info['backend']!r} ({info['device_kind']}, "
            f"{info['device_count']} device(s)) but "
            + (f"JAX_PLATFORMS asked for {want!r}" if asked else
               "no platform was given and the default is 'tpu' — set "
               "JAX_PLATFORMS=cpu explicitly for a CPU-labelled run"))
    return info
