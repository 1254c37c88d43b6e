"""The plain reference against the program at a tiny size, and the control and
the planted faults against the reference: what decides ``correct`` has to pass
the program and fail each of these."""

import json

import pytest
from conftest import TINY
from harness import compare, spec, traffic
from harness.phases import Phases

CELLS = ["tiny-linevul-fusion.finetune", "tiny-linevul.finetune"]


def numbers(prog, ref):
    return compare.numbers("train_steps", prog, ref)


@pytest.fixture(scope="module", params=CELLS)
def readings(request):
    import time

    cell = spec.load_cell(request.param, json.loads(open(TINY).read()))
    cfg = cell["config"]
    reference = spec.load_module("reference", cfg["reference"])
    driver = spec.load_module("drivers", cfg["entry"]).Driver(cfg, reference)
    seed = 2**31 + 77
    data = traffic.generate(cell["cell"]["traffic"], seed, {"n_examples": 2000})
    driver.load(data, reference.make_weights(cfg, seed), seed)
    run = driver.run(Phases(time.time(), driver.setup_steps, 0.0))
    driver.free()
    run["checked_labels"] = [data["labels"][r] for r in run["follow"]["step_rows"]]
    follow = lambda **kw: reference.run(cfg, data, seed, **run["follow"], **kw)
    return cfg, run, follow(), follow


def test_program_follows_the_reference(readings):
    cfg, run, ref, _ = readings
    nums = numbers(run["readings"], ref)
    # float32 on the CPU: the two are the same mathematics to rounding
    assert nums["grad1_gap"] < 1e-4 and nums["delta_gap"] < 1e-3
    assert max(nums[f"loss{i}_gap"] for i in (1, 2, 3)) < 1e-5
    ok, shown = compare.judge(nums, cfg["limits"])
    assert ok and set(shown) == set(cfg["limits"])
    # the real cells' checked batches are all negative (check.labels); here one
    # holds a vulnerable row, so the loss on label 1 is compared too
    assert sum(int(y.sum()) for y in run["checked_labels"]) >= 1
    rows = run["follow"]["step_rows"]
    assert len(rows) == 3 and all(len(set(r)) == len(r) for r in rows)


def test_frozen_ggnn_does_not_move(readings):
    cfg, run, ref, _ = readings
    frozen = [n for n in ref["delta"] if "/flowgnn_encoder/" in n]
    assert bool(frozen) == cfg["use_gnn"]
    for n in frozen:
        assert ref["delta"][n] == 0.0 and run["readings"]["delta"][n] == 0.0
        assert n not in ref["grad1"] and n not in run["readings"]["grad1"]


def test_control_in_fp8_reads_far_above_the_program(readings):
    cfg, run, ref, follow = readings
    program = numbers(run["readings"], ref)
    control = numbers(follow(precision="fp8"), ref)
    assert control["grad1_gap"] > 100 * program["grad1_gap"]
    assert control["grad1_gap"] > 0.005 and control["delta_gap"] > 0.005
    assert not compare.judge(control, cfg["limits"])[0]


@pytest.mark.parametrize("fault,number", [("half_batch", "grad1_gap"),
                                          ("half_batch", "delta_gap"),
                                          ("state_unchanged", "delta_gap")])
def test_planted_fault_fails_its_number(readings, fault, number):
    cfg, _, ref, follow = readings
    nums = numbers(follow(fault=fault), ref)
    assert nums[number] > cfg["limits"][number]
    assert not compare.judge(nums, cfg["limits"])[0]
    if fault == "state_unchanged":
        assert nums["delta_gap"] == 1.0


def test_leaf_rule_on_the_reference_gradient():
    ref = {"loss": [1.0], "grad1": {"a": 1.0, "b": 1.0, "k": 1e-9}, "delta": {"a": 1.0, "b": 1.0, "k": 1.0, "f": 0.0}}
    prog = {"loss": [1.0], "grad1": {"a": 1.0, "b": 1.0, "k": 2e-9}, "delta": {"a": 1.0, "b": 1.0, "k": 5.0, "f": 0.0}}
    nums = numbers(prog, ref)
    assert nums["delta_gap"] == 0.0  # k's gradient is nought to rounding: left out of the change
    assert nums["grad1_gap"] < 1e-8  # measured against the median leaf, not against k
    prog["delta"]["f"] = 0.5         # a frozen leaf that moved is caught
    assert numbers(prog, ref)["delta_gap"] == 0.5
    with pytest.raises(ValueError):
        numbers({**prog, "delta": {"a": 1.0}}, ref)
