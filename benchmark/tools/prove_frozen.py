"""``tools/prove.py`` for a cell whose decoder is frozen: on many seeds in one
process, the numbers ``correct`` compares — the program against the plain
reference (the lower readings) — and, on the first ``--control-seeds`` seeds,
the upper readings. No measured window.

Three kinds of upper reading:

* the control (the reference in fp8) and every planted fault of the reference
  (``FAULTS``) **in the program's place**. A control plays the program: it
  routes by itself (``routing=None``) and hands its choices over, and the good
  reference is run again taking those choices only where rounding explains
  them (``check.route_rule``) — the very rule the program is held to. Faults
  of the decoder are read on the first checked step alone (their numbers need
  no optimizer step); the two faults of the trained part on all of them. Such
  a control has one forward pass, so the numbers that tie the check's forward
  pass to the timed step are not its to give;
* ``--step-faults``: a fault planted **in the program's timed step alone**
  (:func:`plant` active only while the step is traced), the check's own
  forward pass left good. This is what the tie is for: ``hidden_gap`` and
  ``route_gap`` read the good forward pass and see nothing;
* ``--program-faults``: the same plantings in the whole program.

One JSON line a seed, to ``chiprun_out/`` and to standard output.

    python3 benchmark/tools/prove_frozen.py --workload <name> --seeds 11,12,13 \
        [--control-seeds 2] [--faults a,b] [--step-faults expert_skipped,count_off] \
        [--program-faults bias_ignored_sparse] [--benchmark-file ...]
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

TRAINED_PART = ("half_batch", "state_unchanged")
PLANTABLE = ("no_shortcut", "zero_experts_return_0", "bias_ignored", "bias_ignored_sparse",
             "renormalised", "expert_skipped", "capacity_limit", "no_rope_scores", "count_off")


def plant(kind: str, setattr_) -> None:
    """Plant ``kind`` in the program's decoder, underneath the driver, through
    ``setattr_(object, name, value)`` (``monkeypatch.setattr``, or
    :func:`planted`'s, which undoes it). ``count_off`` is a fault of the
    ``stats`` path alone: every layer counts one held assignment too many."""
    import jax.numpy as jnp
    from deepdfa_tpu.llm import longcat

    real_route, real_held = longcat.route, longcat.held_expert_ffn

    def route_with(bias_scale=1.0, renorm=False, every=1):
        def route(x, w_r, bias, cfg):
            choice, gates = real_route(x, w_r, bias * bias_scale, cfg)
            if renorm:
                gates = cfg.routed_scaling_factor * gates / gates.sum(-1, keepdims=True)
            if every > 1:  # wrong at one token in ``every``
                good = real_route(x, w_r, bias, cfg)
                hit = (jnp.arange(x.shape[0]) % every == 0)[:, None]
                choice, gates = jnp.where(hit, choice, good[0]), jnp.where(hit, gates, good[1])
            return choice, gates
        return route

    def held_with(edit):
        return lambda u, choice, gates, *w, lo, rows: real_held(
            u, edit(choice, lo, w[0].shape[0]), gates, *w, lo=lo, rows=rows)

    def capped(choice, lo, n):  # each held expert keeps its first 1.25 x mean assignments
        t, k = choice.shape
        flat = choice.reshape(-1)
        held = (flat >= lo) & (flat < lo + n)
        cap = -(-5 * held.sum() // (4 * n))
        rank = jnp.cumsum(flat[:, None] == lo + jnp.arange(n)[None], 0) - 1
        mine = jnp.take_along_axis(rank, jnp.clip(flat - lo, 0, n - 1)[:, None], 1)[:, 0]
        return jnp.where(held & (mine >= cap), -1, flat).reshape(t, k)

    zeros = lambda x, *_: jnp.zeros(x.shape, jnp.float32)
    if kind == "no_shortcut":
        setattr_(longcat, "_zero_experts", zeros)
        setattr_(longcat, "held_expert_ffn", held_with(lambda c, lo, n: c * 0 - 1))
    elif kind == "zero_experts_return_0":
        setattr_(longcat, "_zero_experts", zeros)
    elif kind == "bias_ignored":
        setattr_(longcat, "route", route_with(bias_scale=0.0))
    elif kind == "bias_ignored_sparse":
        from harness import spec
        every = spec.load_module("reference", "longcat_fusion").SPARSE
        setattr_(longcat, "route", route_with(bias_scale=0.0, every=every))
    elif kind == "renormalised":
        setattr_(longcat, "route", route_with(renorm=True))
    elif kind == "expert_skipped":
        setattr_(longcat, "held_expert_ffn",
                 held_with(lambda c, lo, n: jnp.where(c == lo + n // 2, -1, c)))
    elif kind == "capacity_limit":
        setattr_(longcat, "held_expert_ffn", held_with(capped))
    elif kind == "no_rope_scores":
        setattr_(longcat, "rope_interleaved", lambda x, cos, sin: x * 0)
    elif kind == "count_off":
        real_call = longcat.ExpertLayer.__call__

        def call(self, u, token_mask):
            out, counts = real_call(self, u, token_mask)
            return out, {**counts, "held": counts["held"] + 1}
        setattr_(longcat.ExpertLayer, "__call__", call)
    else:
        raise ValueError(f"{kind!r} is not one of {PLANTABLE}")


@contextlib.contextmanager
def planted(kind: str):
    """:func:`plant` for the length of the block."""
    saved = []

    def set_(obj, name, value):
        saved.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    try:
        plant(kind, set_)
        yield
    finally:
        for obj, name, old in reversed(saved):
            setattr(obj, name, old)


def step_alone(driver, kind: str) -> None:
    """Make ``driver``'s timed step, and nothing else of it, carry ``kind``:
    the jitted step is traced at its first call, inside the planting; the
    check's forward pass is traced outside it."""
    real, evaluate = driver._real_steps

    def faulty(*args):
        with planted(kind):
            return real(*args)

    driver._real_steps = (faulty, evaluate)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-seeds", type=int, default=2)
    ap.add_argument("--faults", default=None,
                    help="comma-separated subset of FAULTS (+ fp8); 'none' for none")
    ap.add_argument("--step-faults", default="", help="comma-separated, of PLANTABLE")
    ap.add_argument("--program-faults", default="", help="comma-separated, of PLANTABLE")
    ap.add_argument("--benchmark-file", default=None)
    args = ap.parse_args()

    from deepdfa_tpu import utils
    from harness import compare, spec, traffic
    from harness.phases import Phases

    utils.setup_compile_cache()
    info = utils.require_backend()
    bench = json.loads(Path(args.benchmark_file).read_text()) if args.benchmark_file else None
    cell = spec.load_cell(args.workload, bench)
    cfg = cell["config"]
    reference = spec.load_module("reference", cfg["reference"])
    new_driver = lambda: spec.load_module("drivers", cfg["entry"]).Driver(cfg, reference)
    driver = new_driver()
    controls = args.faults.split(",") if args.faults else ["fp8", *reference.FAULTS]
    controls = [c for c in controls if c != "none"]
    out_dir = BENCH.parent / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    numbers = lambda a, b: compare.numbers(reference.COMPARISON, a, b)

    def judged(nums: dict) -> dict:
        """The numbers beside ``correct`` under the limits of those given."""
        limits = {k: v for k, v in cfg["limits"].items() if k in nums}
        return {**{k: v for k, v in nums.items() if not k.endswith("_at") or k == "step_count_at"},
                "correct": compare.judge(nums, limits)[0]}

    def program(drv, data, seed, loaded=lambda drv: None):
        t0 = time.time()
        drv.load(data, reference.make_weights(cfg, seed), seed)
        loaded(drv)
        run = drv.run(Phases(t0, drv.setup_steps, 0.0))
        drv.free()
        return run

    with open(out_dir / f"prove_{args.workload}.jsonl", "a") as sink:
        for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
            t0 = time.time()
            data = traffic.generate(cell["cell"]["traffic"], seed)
            run = program(driver, data, seed)
            t1 = time.time()
            follow = run["follow"]
            ref = reference.run(cfg, data, seed, **follow)
            nums = numbers(run["readings"], ref)
            row = {"workload": args.workload, "seed": seed, "device": info["device_kind"],
                   "program": nums, "correct": compare.judge(nums, cfg["limits"])[0],
                   "program_s": t1 - t0, "reference_s": time.time() - t1,
                   "loss": run["readings"]["loss"], "ref_loss": ref["loss"],
                   "step_counts": run["readings"]["tie"]["step_counts"]}
            first = i < args.control_seeds
            for name in controls if first else ():
                kw = {"precision": "fp8"} if name == "fp8" else {"fault": name}
                if name in TRAINED_PART:
                    other, good = reference.run(cfg, data, seed, **follow, **kw), ref
                else:
                    one = {**follow, "step_rows": follow["step_rows"][:1], "routing": None}
                    other = reference.run(cfg, data, seed, **one, **kw)
                    good = reference.run(cfg, data, seed, **{**one, "routing": other["routing"]})
                row[name] = judged(numbers(other, good))
            for where, kinds in (("step", args.step_faults), ("program", args.program_faults)):
                for kind in (k for k in kinds.split(",") if k and first):
                    other = new_driver()
                    if where == "step":
                        run = program(other, data, seed, lambda drv: step_alone(drv, kind))
                    else:
                        with planted(kind):
                            run = program(other, data, seed)
                    good = reference.run(cfg, data, seed, **run["follow"])
                    row[f"{where}:{kind}"] = {
                        **judged(numbers(run["readings"], good)),
                        "step_counts": run["readings"]["tie"]["step_counts"]}
                    del other, run
            line = json.dumps(row)
            sink.write(line + "\n")
            sink.flush()
            print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
