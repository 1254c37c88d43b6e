"""One Brumby layer's retention alone, on the chip: ``ops/power_retention``'s
kernel at each count of feature-map tiles a loop trip, one JSON line each.

The shape is ``brumby-14b-msivd.joint-8k``'s: ``[2, 8192]`` x 40 query heads
over 8 key/value heads of 128, chunks of 128, bfloat16 q / k / v as the
projections leave them, float32 log-gates, rows left-padded to ``--lengths``
real tokens (7,100 in all by default, as that cell's checked batches hold). A
reading is the median of ``--repeats`` timed passes over ``--batches``
batches, in ms a call; ``us_per_chunk_step`` divides it by the (row, key/value
head, chunk) grid steps the kernel visits; ``roofline_share`` is the
operations the retention needs at the real tokens (``2 D (d + 1)`` a token a
head, query and key/value heads, ``D = d (d + 1) / 2``) at the chip's
bfloat16 peak (``bench.NOMINAL_BF16_TFLOPS``) over that time. ``err`` is the
largest difference from the plain chunked form over the real tokens, as a
share of its largest value. Not imported by any cell;
``--rehearse`` runs a small shape under the Pallas interpreter on the CPU (no
time is printed as a device's).

    python scripts/bench_power_retention.py [--trips 5,13] [--out chiprun_out/retention.jsonl]
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def operands(rng, b, s, h, hk, d, lengths):
    mask = np.arange(s)[None, :] >= (s - np.asarray(lengths))[:, None]
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape, np.float32), jnp.bfloat16)
    log_g = -rng.uniform(0.0, 0.08, (b, s, hk)).astype(np.float32)
    return (normal(b, s, h * d), normal(b, s, hk * d), normal(b, s, hk * d), jnp.asarray(log_g),
            jnp.asarray(mask))


def main():
    import bench
    from deepdfa_tpu.ops import power_retention_kernel as kernel
    from deepdfa_tpu.ops.power_retention import chunks_computed, power_retention_plain

    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="2,8192,40,8", help="b,s,query heads,key/value heads")
    ap.add_argument("--lengths", default="4000,3100", help="real tokens a row, ',' between them")
    ap.add_argument("--chunk", type=int, default=128)
    ap.add_argument("--trips", default=None,
                    help="tiles a loop trip, ',' between them; each divides 65 (default: TRIP)")
    ap.add_argument("--batches", type=int, default=3)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    backend, kind = bench.start_on_device()
    d = kernel.LANES
    if args.rehearse:
        (b, s, h, hk), lengths, chunk = (2, 128, 4, 2), [100, 45], 32
    else:
        b, s, h, hk = map(int, args.shape.split(","))
        lengths, chunk = [int(n) for n in args.lengths.split(",")], args.chunk
    rng = np.random.default_rng(args.seed)
    batches = [operands(rng, b, s, h, hk, d, lengths) for _ in range(args.batches)]
    mask = batches[0][-1]
    visited = int(chunks_computed(mask, chunk, fused=True)) * hk
    feature = d * (d + 1) // 2
    needed = 2 * feature * (d + 1) * (h + hk) * sum(lengths)

    q, k, v, log_g, _ = batches[0]
    split = lambda x, heads: x.reshape(b, s, heads, d)
    want = np.asarray(jax.jit(functools.partial(power_retention_plain, chunk=chunk))(
        split(q, h), split(k, hk), split(v, hk), log_g, mask), np.float32).reshape(b, s, h * d)
    real = np.asarray(mask)

    rows = []
    for trip in map(int, args.trips.split(",")) if args.trips else [kernel.TRIP]:
        kernel.TRIP = trip  # read where the kernel is traced: a fresh jit traces it again
        f = jax.jit(functools.partial(kernel.retention_forward.__wrapped__, chunk=chunk,
                                      interpret=args.rehearse))
        t0 = time.perf_counter()
        first = np.asarray(jax.block_until_ready(f(*batches[0])), np.float32)
        first_s = time.perf_counter() - t0
        times = []
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            for ops in batches:
                out = f(*ops)
            jax.block_until_ready(out)
            times.append((time.perf_counter() - t0) / len(batches))
        err = float(np.abs(first - want)[real].max() / np.abs(want[real]).max())
        row = dict(trip=trip, shape=[b, s, h, hk, d], chunk=chunk, lengths=lengths,
                   chunk_steps=visited, device=kind, backend=backend,
                   first_call_s=round(first_s, 2), err=err)
        if backend == "tpu":  # a CPU's time is no device's
            ms = statistics.median(times) * 1e3
            peak = bench.NOMINAL_BF16_TFLOPS[kind] * 1e12
            row.update(ms=ms, min_ms=min(times) * 1e3, us_per_chunk_step=ms * 1e3 / visited,
                       roofline_share=100 * needed / peak / (ms * 1e-3))
        print(json.dumps(row), flush=True)
        rows.append(row)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            fh.writelines(json.dumps(r) + "\n" for r in rows)


if __name__ == "__main__":
    main()
