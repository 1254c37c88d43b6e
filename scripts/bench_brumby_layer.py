"""One Brumby layer on the chip: its position-wise halves over the chunks of
each width that hold a real token, against the whole block, one JSON line
each.

The shape is ``brumby-14b-msivd.joint-8k``'s: a ``BrumbyLayer`` of
Brumby-14B-Base at ``[2, 8192, 5120]`` (weights from ``init``, bfloat16; the
retention on its kernel), over three batches whose rows are left-padded to the
real lengths of that cell's three checked batches (``--lengths``: 5903, 2428 |
8192, 1786 | 7503, 2490). A reading is the median of ``--repeats`` timed passes
over the batches, in ms a layer; ``computed_share`` is the share of the
block's positions the halves computed (live chunks times the width, over the
three batches); ``err`` is the largest difference from the whole block over
the real tokens, as a share of its largest value, and ``mean_err`` a real
token's relative L2 difference from it, averaged. Not imported by any cell;
``--rehearse`` runs the tiny layer on the CPU (no time is printed as a
device's).

    python scripts/bench_brumby_layer.py [--widths 512,1024,2048] [--out chiprun_out/layer.jsonl]
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


def main():
    import bench
    from deepdfa_tpu.llm import brumby

    ap = argparse.ArgumentParser()
    ap.add_argument("--lengths", default="5903,2428;8192,1786;7503,2490",
                    help="real tokens a row, ',' between rows and ';' between batches")
    ap.add_argument("--widths", default="512,1024,2048",
                    help="positions a chunk, ',' between them; each divides the block")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    backend, kind = bench.start_on_device()
    if args.rehearse:
        cfg, s, lengths, widths = brumby.tiny_brumby(), 64, [[40, 9], [64, 5], [50, 20]], [16, 32]
    else:
        cfg, s = brumby.brumby_14b(), 8192
        lengths = [[int(n) for n in rows.split(",")] for rows in args.lengths.split(";")]
        widths = [int(w) for w in args.widths.split(",")]
    rng = np.random.default_rng(args.seed)

    def batch(rows):
        mask = np.arange(s)[None, :] >= (s - np.asarray(rows))[:, None]
        positions = np.maximum(np.cumsum(mask, axis=-1) - 1, 0).astype(np.int32)
        x = rng.standard_normal((len(rows), s, cfg.hidden_size), np.float32)
        return jnp.asarray(x, jnp.dtype(cfg.dtype)), jnp.asarray(mask), jnp.asarray(positions)

    batches = [batch(rows) for rows in lengths]
    layer = brumby.BrumbyLayer(cfg)
    params = jax.jit(layer.init)(jax.random.key(args.seed), *batches[0])
    real = [np.asarray(m) for _, m, _ in batches]
    blocks = sum(m.size for m in real)

    want = None
    rows = []
    for width in [None, *widths]:
        if width is None:
            f = jax.jit(lambda p, *a: layer.apply(p, *a, method=brumby.BrumbyLayer.whole))
            computed = blocks
        else:
            brumby.DENSE_BLOCK = width  # read where the layer is traced: a fresh jit traces it again
            f = jax.jit(lambda p, *a: layer.apply(p, *a)[0])
            computed = 0
            for _, mask, _ in batches:
                live, w = brumby.dense_chunks(mask)
                computed += int(jnp.sum(live)) * w
        t0 = time.perf_counter()
        outs = [np.asarray(jax.block_until_ready(f(params, *ops)), np.float32) for ops in batches]
        first_s = time.perf_counter() - t0
        want = outs if want is None else want
        err = max(float(np.abs(o - w)[m].max() / np.abs(w[m]).max())
                  for o, w, m in zip(outs, want, real))
        gaps = np.concatenate([np.linalg.norm(o - w, axis=-1)[m] / np.linalg.norm(w, axis=-1)[m]
                               for o, w, m in zip(outs, want, real)])
        times = []
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            for ops in batches:
                out = f(params, *ops)
            jax.block_until_ready(out)
            times.append((time.perf_counter() - t0) / len(batches))
        row = dict(width=width or "whole", shape=[2, s, cfg.hidden_size], lengths=lengths,
                   computed_share=100 * computed / blocks,
                   real_share=100 * sum(int(m.sum()) for m in real) / blocks,
                   device=kind, backend=backend, first_call_s=round(first_s, 2), err=err,
                   mean_err=float(gaps.mean()))
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
