"""The trace reduction, on a hand-made trace and on a recorded one: two whole
steps of linevul-fusion.finetune on a TPU v5 lite (my chip run, PR 25), op
names already cut by ``short_name``."""

import gzip
import json

import numpy as np
from conftest import BENCH
from harness.trace import reduce, short_name

DEV, OPS = "/device:TPU:0", "XLA Ops"


def test_hand_made_trace():
    rows = [
        [DEV, OPS, "%a = f32[2]{0} fusion(...)", 100, 50],     # 100-150
        [DEV, OPS, "%b = f32[2]{0} fusion(...)", 140, 60],     # overlaps: union 100-200
        [DEV, OPS, "%a = f32[2]{0} fusion(...)", 400, 100],    # 400-500
        ["/host:CPU", "python", "bench:step.dispatch", 0, 100],    # covers the lead-in gap
        ["/host:CPU", "python", "bench:loss.sync", 100, 150],
        ["/host:CPU", "python", "bench:data.wait", 250, 140],      # covers most of 200-400
        ["/host:CPU", "python", "bench:step.dispatch", 390, 210],
    ]
    out = reduce(rows)
    assert out["window_s"] == 600e-9 and out["busy_s"] == 200e-9
    assert out["device_ops"] == [["%a f32[2]", 150e-9], ["%b f32[2]", 60e-9]]
    gaps = dict(map(tuple, out["idle_gaps"]))
    assert gaps == {"data.wait": 200e-9, "step.dispatch": 200e-9}
    assert reduce([r for r in rows if r[0] != DEV]) is None


def test_two_device_planes_are_averaged():
    rows = [[DEV, OPS, "%a = f32[1]{0} x", 0, 100],
            ["/device:TPU:1", OPS, "%a = f32[1]{0} x", 0, 50]]
    out = reduce(rows)
    assert out["busy_s"] == 75e-9 and out["window_s"] == 100e-9


def test_short_name():
    assert short_name("%fusion.10 = f32[65536,128]{1,0:T(8,128)S(1)} fusion(s32[1]") \
        == "%fusion.10 f32[65536,128]"
    assert short_name("%fusion.4 = (f32[5,7]{1,0}, f32[5,7]{1,0}) fusion(") == "%fusion.4 f32[5,7]"
    assert short_name("%copy-done.3 = bf16[8]{0} copy-done(") == "%copy-done.3 bf16[8]"


def test_recorded_trace():
    with gzip.open(BENCH / "tests" / "data" / "trace_two_steps.json.gz", "rt") as f:
        rows = json.load(f)
    out = reduce(rows)
    # busy time again, by painting a 100 ns raster instead of merging intervals
    dev = [(r[3], r[3] + r[4]) for r in rows if r[0].startswith("/device")]
    t0 = min(r[3] for r in rows)
    t1 = max(r[3] + r[4] for r in rows)
    raster = np.zeros((t1 - t0) // 100 + 1, bool)
    for a, b in dev:
        raster[(a - t0) // 100:(b - t0 + 99) // 100] = True
    assert abs(raster.sum() * 100e-9 - out["busy_s"]) < 0.02 * out["busy_s"]
    assert abs(out["window_s"] - (t1 - t0) / 1e9) < 1e-12
    # as read on the chip: two steps of about 140 ms, the device busy 69% of them,
    # idle almost only while the host is inside the call into the jitted step
    assert 0.27 < out["window_s"] < 0.29 and 0.68 < out["busy_s"] / out["window_s"] < 0.70
    assert out["idle_gaps"][0][0] == "step.dispatch"
    assert out["idle_gaps"][0][1] > 0.9 * (out["window_s"] - out["busy_s"])
    assert len(out["device_ops"]) == 10 and out["device_ops"][0][0].startswith("%fusion")
