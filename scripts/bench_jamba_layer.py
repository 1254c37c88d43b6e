"""One Jamba Mamba layer on the chip: its position-wise halves over the chunks
of each width that hold a real token, against the whole block, one JSON line
each.

The shape is ``jamba2-3b-msivd.joint-2k``'s: a Mamba ``JambaLayer`` of
AI21-Jamba2-3B at ``[4, 2048, 2560]`` (weights from ``init``, bfloat16; the
scan on its kernel where the device takes it), over three batches whose rows
are left-padded to the real lengths of that cell's three checked batches
(``--lengths``: the traffic's ``size_seed`` rows in the program's shuffle of
epoch 0). A reading is the median of ``--repeats`` timed passes over the
batches, in ms a layer; ``computed_share`` is the share of the block's
positions the halves computed (live chunks times the width, over the three
batches); ``err`` is the largest difference from the whole block over the
real tokens, as a share of its largest value, and ``mean_err`` a real
token's relative L2 difference from it, averaged. Not imported by any cell;
``--rehearse`` runs the tiny layer on the CPU (no time is printed as a
device's).

    python scripts/bench_jamba_layer.py [--widths 256,512,1024] [--out <file.jsonl>]
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
    from deepdfa_tpu.llm import jamba
    from deepdfa_tpu.llm.layers import dense_chunks

    ap = argparse.ArgumentParser()
    ap.add_argument("--lengths", default="2048,1201,1940,1273;679,576,2048,961;964,2048,1287,302",
                    help="real tokens a row, ',' between rows and ';' between batches")
    ap.add_argument("--widths", default="256,512,1024",
                    help="positions a chunk, ',' between them; each divides the block")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    backend, kind = bench.start_on_device()
    if args.rehearse:
        cfg, s = jamba.tiny_jamba(), 64
        lengths, widths = [[40, 9, 64, 1], [64, 5, 33, 17], [50, 20, 2, 64]], [16, 32]
    else:
        cfg, s = jamba.jamba2_3b(), 2048
        lengths = [[int(n) for n in rows.split(",")] for rows in args.lengths.split(";")]
        widths = [int(w) for w in args.widths.split(",")]
    rng = np.random.default_rng(args.seed)

    def batch(rows):
        mask = np.arange(s)[None, :] >= (s - np.asarray(rows))[:, None]
        x = rng.standard_normal((len(rows), s, cfg.hidden_size), np.float32)
        return jnp.asarray(x, jnp.dtype(cfg.dtype)), jnp.asarray(mask)

    batches = [batch(rows) for rows in lengths]
    layer = jamba.JambaLayer(cfg, False)
    params = jax.jit(layer.init)(jax.random.key(args.seed), *batches[0])
    real = [np.asarray(m) for _, m in batches]
    blocks = sum(m.size for m in real)

    want = None
    rows = []
    for width in [None, *widths]:
        if width is None:
            f = jax.jit(lambda p, *a: layer.apply(p, *a, method=jamba.JambaLayer.whole))
            computed = blocks
        else:
            jamba.DENSE_BLOCK = width  # read where the layer is traced: a fresh jit traces it again
            f = jax.jit(layer.apply)
            computed = 0
            for _, mask in batches:
                live, w = dense_chunks(mask, width)
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
        row = dict(width=width or "whole", shape=[len(lengths[0]), s, cfg.hidden_size],
                   lengths=lengths, computed_share=100 * computed / blocks,
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
