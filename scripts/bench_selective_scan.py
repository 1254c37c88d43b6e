"""One Mamba layer's ``scan`` scope alone, on the chip: ``ops/selective_scan.gated_scan``
in its plain form and as the kernel at its tile choices, with and without
padding, one JSON line each.

The shape is ``jamba2-3b-msivd.joint-2k``'s: ``[4, 2048, 5120]`` x 16 states,
bfloat16 operands as the mixer's products leave them, rows left-padded to
lengths drawn as that cell's traffic draws them (lognormal, median 700, sigma
0.9, clipped to [16, 2048]). A reading is the median of ``--repeats`` timed
passes over ``--batches`` batches of 4 rows, in ms a call; ``err`` is the
largest difference from the plain form over the real tokens, as a share of
its largest value. Not imported by any cell; ``--rehearse`` runs a small shape
under the Pallas interpreter on the CPU (no time is printed as a device's).

    python scripts/bench_selective_scan.py [--tiles '128,8;256,8'] [--out chiprun_out/scan.jsonl]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def operands(rng, b, s, d, n, lengths, dtype):
    from deepdfa_tpu.llm.jamba import dt_bias_init

    mask = np.arange(s)[None, :] >= (s - np.asarray(lengths))[:, None]
    normal = lambda *shape: rng.normal(size=shape).astype(np.float32)
    cast = lambda v: jnp.asarray(v, dtype)
    return dict(
        c=cast(normal(b, s, d) * mask[..., None]), dt=cast(normal(b, s, d)),
        dt_bias=dt_bias_init(jax.random.key(int(rng.integers(2**31))), (d,)),
        A=jnp.asarray(-np.exp(normal(d, n))), B=cast(normal(b, s, n)), C=cast(normal(b, s, n)),
        D=jnp.ones((d,), jnp.float32), z=cast(normal(b, s, d)), mask=jnp.asarray(mask))


def main():
    import bench
    from deepdfa_tpu.ops.selective_scan import gated_scan
    from deepdfa_tpu.ops.selective_scan_kernel import scan_forward

    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="4,2048,5120,16", help="b,s,d_inner,d_state")
    ap.add_argument("--tiles", default="128,8;64,8;256,8;128,4;128,16",
                    help="the kernel's chunk,unroll choices, ';' between them")
    ap.add_argument("--batches", type=int, default=6)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=36)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    backend, kind = bench.start_on_device()
    b, s, d, n = ((2, 64, 1024, 16) if args.rehearse else map(int, args.shape.split(",")))
    tiles = "16,8;32,4" if args.rehearse else args.tiles
    rng = np.random.default_rng(args.seed)
    draw = lambda: np.clip(np.exp(rng.normal(np.log(700 * s / 2048), 0.9, size=b)), 16 * s // 2048, s
                           ).astype(int)
    padded = [operands(rng, b, s, d, n, draw(), jnp.bfloat16) for _ in range(args.batches)]
    full = [dict(ops, mask=jnp.ones((b, s), bool)) for ops in padded[:2]]

    variants = [("plain", {}, lambda ops: gated_scan(**ops))]
    for choice in tiles.split(";"):
        kw = dict(zip(("chunk", "unroll"), map(int, choice.split(","))))
        variants.append(("kernel", kw, lambda ops, kw=kw: scan_forward(
            *(ops[k] for k in ("c", "dt", "dt_bias", "A", "B", "C", "D", "z", "mask")),
            interpret=args.rehearse, **kw)))

    rows, want = [], {}
    for padding, batches in (("left-padded", padded), ("none", full)):
        pad_share = 100 * float(np.mean([1 - np.asarray(ops["mask"]).mean() for ops in batches]))
        for name, kw, fn in variants:
            f = jax.jit(fn)
            t0 = time.perf_counter()
            first = jax.block_until_ready(f(batches[0]))
            first_s = time.perf_counter() - t0
            times = []
            for _ in range(args.repeats):
                t0 = time.perf_counter()
                for ops in batches:
                    out = f(ops)
                jax.block_until_ready(out)
                times.append((time.perf_counter() - t0) / len(batches))
            got = np.asarray(first.astype(jnp.float32))
            if name == "plain":
                want[padding] = got
            real = np.asarray(batches[0]["mask"])
            err = float(np.abs(got - want[padding])[real].max() / np.abs(want[padding]).max())
            row = dict(name=name, **kw, padding=padding, pad_share=round(pad_share, 1),
                       shape=[b, s, d, n], device=kind, backend=backend,
                       first_call_s=round(first_s, 2), err=err)
            if backend == "tpu":  # a CPU's time is no device's
                row.update(ms=statistics.median(times) * 1e3, min_ms=min(times) * 1e3)
            print(json.dumps(row), flush=True)
            rows.append(row)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            fh.writelines(json.dumps(r) + "\n" for r in rows)


if __name__ == "__main__":
    main()
