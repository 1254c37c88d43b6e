"""The driver's multichip gate must be hermetic: the parent process never
initialises a jax backend (the virtual device count is an XLA flag read at
backend init, and a parent holding the real chip must not be asked for CPU
devices), and the re-exec'd child gets a clean CPU-mesh environment.

The real end-to-end payload is exercised by the driver itself and by
``python __graft_entry__.py``; here we pin the *contract*.
"""

import subprocess

import pytest

import __graft_entry__ as g


def test_parent_never_initialises_backend(monkeypatch):
    """dryrun_multichip must reach the subprocess spawn without ever
    calling jax.devices()."""
    monkeypatch.delenv("_DEEPDFA_DRYRUN_CHILD", raising=False)

    def _boom(*a, **k):
        raise AssertionError("parent touched jax.devices() — gate not hermetic")

    monkeypatch.setattr(g.jax, "devices", _boom)

    captured = {}

    def _fake_run(cmd, env=None, cwd=None, timeout=None):
        captured.update(cmd=cmd, env=env, timeout=timeout)
        return subprocess.CompletedProcess(cmd, 0)

    monkeypatch.setattr(g.subprocess, "run", _fake_run)
    g.dryrun_multichip(8)

    env = captured["env"]
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["_DEEPDFA_DRYRUN_CHILD"] == "1"
    assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
    assert captured["timeout"] <= 300


def test_child_failure_propagates(monkeypatch):
    monkeypatch.delenv("_DEEPDFA_DRYRUN_CHILD", raising=False)
    monkeypatch.setattr(
        g.subprocess, "run",
        lambda cmd, **k: subprocess.CompletedProcess(cmd, 7))
    with pytest.raises(RuntimeError, match="rc=7"):
        g.dryrun_multichip(8)


def test_child_runs_payload_inline(monkeypatch):
    """When already the child, the payload runs in-process (no re-exec loop).
    conftest pins an 8-device CPU platform, so the real payload works here —
    but to keep the suite fast we only check routing: the subprocess layer
    must NOT be invoked."""
    monkeypatch.setenv("_DEEPDFA_DRYRUN_CHILD", "1")

    def _no_reexec(*a, **k):
        raise AssertionError("child re-exec'd — infinite spawn loop")

    monkeypatch.setattr(g.subprocess, "run", _no_reexec)
    # n_devices=16 > the 8 virtual devices: the child must fail loudly
    # rather than silently re-spawning.
    with pytest.raises(RuntimeError, match="sees 8 < 16"):
        g.dryrun_multichip(16)
