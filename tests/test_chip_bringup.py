"""What replaced the survive-without-a-chip scaffolding: a compile cache
that can be placed from outside, entry points that say where they ran, and
a chip smoke that refuses anything but the chip.

The cache tests run child processes with their own environment (the suite
itself runs with the cache disabled — tests/conftest.py)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

_COMPILE = (
    "import json, os, sys\n"
    "import jax, jax.numpy as jnp\n"
    "from deepdfa_tpu import utils\n"
    "where = utils.setup_compile_cache()\n"
    "jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((32, 32))).block_until_ready()\n"
    "print(json.dumps({'where': str(where),\n"
    "                  'config_dir': jax.config.jax_compilation_cache_dir,\n"
    "                  'min_secs': jax.config.jax_persistent_cache_min_compile_time_secs}))\n"
)


def _run(code: str, cwd: Path = REPO, **env_extra) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "PYTHONPATH")}
    env |= {"JAX_PLATFORMS": "cpu", "TF_CPP_MIN_LOG_LEVEL": "3"} | env_extra
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_compile_cache_goes_where_the_variable_says(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the program sets no directory in
    code: entries land under it, and a second fresh process adds none."""
    cache = tmp_path / "elsewhere"
    out = _run(_COMPILE, JAX_COMPILATION_CACHE_DIR=str(cache))
    assert out["where"] == out["config_dir"] == str(cache)
    assert out["min_secs"] == 0.0  # small GGNN programs must persist too
    first = sorted(p.name for p in cache.iterdir())
    assert first, "nothing persisted under the variable's directory"
    _run(_COMPILE, JAX_COMPILATION_CACHE_DIR=str(cache))
    assert sorted(p.name for p in cache.iterdir()) == first


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(tmp_path):
    """Unset: <project_dir>/.jax_cache — never a path made from a pid, a
    run id, tempfile or the clock (the path is part of the cache key).
    Checked on a copy of the package so the real checkout stays clean."""
    import shutil

    checkout = tmp_path / "checkout"
    shutil.copytree(REPO / "deepdfa_tpu", checkout / "deepdfa_tpu",
                    ignore=shutil.ignore_patterns("__pycache__"))
    outs = [_run(_COMPILE, cwd=checkout) for _ in range(2)]
    want = str(checkout / ".jax_cache")
    assert [o["where"] for o in outs] == [want, want]
    assert [o["config_dir"] for o in outs] == [want, want]
    assert any((checkout / ".jax_cache").iterdir())


def test_no_other_cache_directory_is_set_in_code():
    hits = []
    for path in [*REPO.glob("*.py"), *REPO.glob("scripts/*.py"),
                 *REPO.glob("deepdfa_tpu/**/*.py")]:
        text = path.read_text()
        if "jax_compilation_cache_dir" in text and "def setup_compile_cache" not in text:
            hits.append(str(path.relative_to(REPO)))
    assert hits == []


def test_fit_journal_carries_per_epoch_step_accounting(tmp_path, monkeypatch):
    """The completed journal record lists, per epoch, the steps, the
    compiles and the twin-routed steps — what chip_smoke checks."""
    monkeypatch.setenv("DEEPDFA_STORAGE", str(tmp_path / "storage"))
    from deepdfa_tpu.train import cli

    run_dir = tmp_path / "run"
    cli.main(["fit", "--run-dir", str(run_dir), "--set", "data.sample=true",
              "--set", "optim.max_epochs=2", "--set", "model.hidden_dim=8",
              "--set", "model.n_steps=2"])
    journal = json.loads((run_dir / "journal.json").read_text())
    assert journal["completed"] is True
    rows = journal["epochs"]
    assert [r["epoch"] for r in rows] == [0, 1]
    assert all(r["twin_routed_steps"] == 0 for r in rows)
    assert rows[0]["telemetry"]["compiles"] >= 1
    assert rows[1]["telemetry"]["compiles"] == 0
    log = (run_dir / "run.log").read_text()
    assert ": fit backend=cpu device_kind='cpu' devices=" in log
    # ...which is the statement chip_smoke's parent reads back
    sys.path.insert(0, str(REPO))
    import chip_smoke

    where = chip_smoke.Smoke(rehearse=True)._run_log_where(run_dir, "fit")
    assert where["backend"] == "cpu" and where["device_kind"] == "cpu"
    with pytest.raises(chip_smoke.StageFailed, match="no backend statement"):
        chip_smoke.Smoke(rehearse=True)._run_log_where(run_dir, "serve")


def _smoke(*args, cwd=REPO, script=REPO / "chip_smoke.py", **env_extra):
    env = dict(os.environ) | {"JAX_PLATFORMS": "cpu"} | env_extra
    return subprocess.run([sys.executable, str(script), *args], env=env,
                          cwd=cwd, capture_output=True, text=True,
                          timeout=300)


def test_chip_smoke_refuses_a_cpu_backend():
    """No chip: non-zero exit, the reason on stderr, no result line — even
    with JAX_PLATFORMS=cpu in the caller's environment."""
    proc = _smoke()
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "FAILED" in proc.stderr and "device" in proc.stderr
    assert "Unable to initialize backend 'tpu'" in proc.stderr


def test_chip_smoke_alone_is_not_the_program(tmp_path):
    import shutil

    lone = tmp_path / "chip_smoke.py"
    shutil.copy(REPO / "chip_smoke.py", lone)
    proc = _smoke(cwd=tmp_path, script=lone)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "not a checkout" in proc.stderr


def test_parents_of_device_processes_never_import_jax():
    """chip_smoke's parent, the HPO sweep parent and the replica launcher
    start children that need the chip; a parent that has touched JAX holds
    it. None of them may even import JAX."""
    code = (
        "import sys\n"
        "sys.path.insert(0, %r)\n"
        "import chip_smoke\n"
        "import deepdfa_tpu.train.tune\n"
        "from deepdfa_tpu.serve import SubprocessLauncher\n"
        "print('jax' in sys.modules or 'jaxlib' in sys.modules)\n"
    ) % str(REPO)
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-1000:]
    assert proc.stdout.strip() == "False"


def test_describe_backend_is_what_jax_reports():
    import jax

    from deepdfa_tpu import utils

    where = utils.describe_backend()
    dev = jax.devices()[0]
    assert where == {"backend": jax.default_backend(),
                     "platform": dev.platform,
                     "device_kind": dev.device_kind,
                     "device_count": len(jax.devices())}


def test_start_on_device_states_the_pinned_platform(monkeypatch):
    """Bench scripts under an explicit JAX_PLATFORMS=cpu keep working and
    label their output cpu."""
    import bench

    said = []
    monkeypatch.setattr(bench, "_progress", said.append)
    assert bench.start_on_device() == ("cpu", "cpu")
    assert "backend=cpu" in said[-1] and "device_kind='cpu'" in said[-1]


def test_native_solver_status_names_the_dataflow_backend():
    from deepdfa_tpu.cpg.analyses import native_solver_status

    status = native_solver_status()
    assert status == "native" or status.startswith("python-fallback: ")


def test_launcher_chip_env_is_one_chip_per_process():
    from deepdfa_tpu.serve import SubprocessLauncher

    env = SubprocessLauncher.chip_env(3)
    assert env["TPU_VISIBLE_CHIPS"] == "3"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"


@pytest.mark.slow
def test_chip_smoke_cpu_rehearsal_passes_and_is_labelled_cpu():
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--rehearse-cpu"],
        cwd=REPO, capture_output=True, text=True, timeout=1800)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": last["device"]["count"]}}
    report = json.loads(proc.stdout.strip().splitlines()[-2])
    assert report["rehearsal"] is True
    assert report["stages"]["kernels"]["interpret"] is True


def test_chip_smoke_fails_a_stage_that_ran_somewhere_else():
    """Every stage states where it ran; one that disagrees with the device
    stage fails the smoke (JAX can drop to CPU with only a warning)."""
    sys.path.insert(0, str(REPO))
    import chip_smoke

    smoke = chip_smoke.Smoke(rehearse=False)
    assert smoke.env["JAX_PLATFORMS"] == "tpu"  # children never get a fallback
    smoke.device = {"backend": "tpu", "device_kind": "TPU v5 lite",
                    "device_count": 1}
    smoke.same_device("fit", {"backend": "tpu",
                              "device_kind": "TPU v5 lite",
                              "device_count": 1})
    with pytest.raises(chip_smoke.StageFailed, match="fit ran on"):
        smoke.same_device("fit", {"backend": "cpu", "device_kind": "cpu",
                                  "device_count": 1})
    with pytest.raises(chip_smoke.StageFailed, match="serve ran on"):
        smoke.same_device("serve", {"backend": "tpu",
                                    "device_kind": "TPU v5 lite",
                                    "device_count": 4})


def test_chip_smoke_requests_reach_past_the_first_serve_bucket():
    """One of the smoke's sources must be larger than the 126-node size
    class, or only the smallest serve bucket ever answers a request."""
    sys.path.insert(0, str(REPO))
    import chip_smoke
    from deepdfa_tpu.pipeline import encode_source
    from deepdfa_tpu.serve.engine import serve_buckets

    small, mid, _big = serve_buckets(16)
    (long_fn,) = encode_source(chip_smoke._long_function(), {},
                               keep_cpg=False)
    assert not small.admits(long_fn.graph) and mid.admits(long_fn.graph)
    for code in chip_smoke._SOURCES.values():
        for fn in encode_source(code, {}, keep_cpg=False):
            assert small.admits(fn.graph), fn.name
