"""``run.py`` end to end at a tiny size: the result line, the look for a chip,
and ``correct`` coming out false with the timed path broken underneath."""

import json
import os
import shutil
import subprocess
import sys

import pytest
from conftest import BENCH, TINY

ROOT = BENCH.parent
ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "TF_CPP_MIN_LOG_LEVEL": "3",
       "JAX_COMPILATION_CACHE_DIR": ""}


def _run(args, cwd=ROOT, env=ENV):
    return subprocess.run([sys.executable, "benchmark/run.py", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("trace", [0, 1])
def test_well_formed_last_line_labelled_cpu(trace, tmp_path):
    env = {**ENV, "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"), "TMPDIR": str(tmp_path)}
    proc = _run(["--workload", "tiny-linevul-fusion.finetune", "--seed", str(2**31 + 5),
                 "--seconds", "1", "--trace", str(trace), "--benchmark-file", TINY], env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(row)[-1] == "compared" and row["correct"] is True
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(row)
    assert row["device"]["platform"] == "cpu" and row["device"]["count"] == 1
    assert row["attempted"] > 0 and row["failed"] == 0
    bench = json.loads(open(TINY).read())
    if trace:
        # no device metric off the TPU: the readers return nothing, the line leaves them out
        assert set(row["metrics"]) == {"data_wait_share.train", "pad_share_nodes.train",
                                       "dispatch_ms.train", "dispatch_p95_ms.train",
                                       "loss_sync_share.train"}
        assert 50 < row["metrics"]["pad_share_nodes.train"]["value"] < 100
        assert not list(tmp_path.glob("bench_trace_*")), "the trace directory is removed"
    else:
        assert set(row["metrics"]) == {m["name"] for m in bench["end_to_end"]}
        assert row["metrics"]["train_functions_per_s"]["value"] > 0
        assert row["metrics"]["setup_s"]["value"] > 0
    for m in row["metrics"].values():
        assert set(m) == {"value", "unit"}
    tail = proc.stderr.strip().splitlines()[-3:]
    assert tail[-1] == "correct: True" and all(t.startswith("compared ") for t in tail[:-1])


def test_no_result_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(["--workload", "linevul.finetune", "--seed", "1", "--seconds", "1",
                 "--trace", "0"], cwd=tmp_path)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_fewer_chips_than_the_cell_asks_for(tmp_path):
    bench = json.loads(open(TINY).read())
    bench["workloads"][0]["chips"] = 4
    (tmp_path / "b.json").write_text(json.dumps(bench))
    proc = _run(["--workload", bench["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
                 "--trace", "0", "--benchmark-file", str(tmp_path / "b.json")])
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "asks for 4 chip" in proc.stderr


def test_checked_batch_with_a_vulnerable_row_is_refused_where_the_config_states_none(
        tmp_path, capsys):
    """The real configurations state ``check.labels = all_negative``; the tiny
    mix's first batch holds vulnerable rows, so the same statement there has
    to stop the run, loudly."""
    sys.path.insert(0, str(BENCH))
    import run

    bench = json.loads(open(TINY).read())
    entry = next(c for c in bench["configs"] if c["name"] == "tiny-linevul")
    cfg = json.loads((ROOT / entry["file"]).read_text())
    cfg["check"]["labels"] = "all_negative"
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    entry["file"] = str(tmp_path / "cfg.json")
    (tmp_path / "b.json").write_text(json.dumps(bench))
    with pytest.raises(RuntimeError, match="holds a vulnerable function"):
        run.main(["--workload", "tiny-linevul.finetune", "--seed", "3", "--seconds", "0.2",
                  "--trace", "0", "--benchmark-file", str(tmp_path / "b.json")])
    assert capsys.readouterr().out.strip() == ""


def _broken(kind):
    """``make_joint_steps`` with the train step broken underneath the driver."""
    import jax.numpy as jnp
    from deepdfa_tpu.llm import joint

    real_make = joint.make_joint_steps

    def make(*a, **kw):
        train, evaluate = real_make(*a, **kw)

        def state_unchanged(state, llm, jb):
            _, loss, probs = train(state, llm, jb)
            return state, loss, probs

        def half_batch(state, llm, jb):
            keep = jnp.arange(jb.mask.shape[0]) < jb.mask.shape[0] // 2
            return train(state, llm, jb._replace(mask=jnp.asarray(jb.mask) & keep))

        return {"state_unchanged": state_unchanged, "half_batch": half_batch}[kind], evaluate

    return make


@pytest.mark.parametrize("workload,kind", [
    ("tiny-linevul-fusion.finetune", "state_unchanged"),
    ("tiny-linevul-fusion.finetune", "half_batch"),
    ("tiny-linevul.finetune", "state_unchanged"),
    ("tiny-linevul.finetune", "half_batch"),
])
def test_correct_is_false_with_the_timed_path_broken(workload, kind, monkeypatch, capsys):
    sys.path.insert(0, str(BENCH))
    import run
    from deepdfa_tpu.llm import joint

    monkeypatch.setattr(joint, "make_joint_steps", _broken(kind))
    assert run.main(["--workload", workload, "--seed", "11", "--seconds", "0.5",
                     "--trace", "0", "--benchmark-file", TINY]) == 0
    out = capsys.readouterr()
    row = json.loads(out.out.strip().splitlines()[-1])
    assert row["correct"] is False
    over = [k for k, v in row["compared"].items() if not v["value"] <= v["limit"]]
    assert over and out.err.strip().splitlines()[-1] == "correct: False"
