"""Test harness: run JAX on a virtual 8-device CPU platform so sharding and
collective paths are exercised without TPU hardware (SURVEY.md §4). Tests
are hermetic and device-free: before any backend initialises we pin the CPU
platform with 8 virtual devices.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")
# entry points place the persistent compile cache inside the checkout
# (utils.setup_compile_cache); the suite must not write there — the tests of
# the cache itself run subprocesses with their own environment
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seed():
    np.random.seed(0)


def pytest_report_header(config):
    return f"jax devices: {jax.device_count()} ({jax.default_backend()})"
