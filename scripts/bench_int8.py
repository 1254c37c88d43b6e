#!/usr/bin/env python
"""Microbenchmark: fused int8-dequant matmul (pallas) vs XLA bf16 matmul vs
XLA dequantize-then-matmul, at CodeLlama-7B projection shapes.

Prints ONE JSON line. The int8 kernel's case is HBM traffic: at low batch
the matmul is weight-bandwidth-bound, and int8-resident weights halve that
term — this measures whether the kernel actually cashes the cheque on real
hardware. Under an explicit ``JAX_PLATFORMS=cpu`` the kernel runs in
interpret mode: correctness only, timings meaningless, flagged in the
output; with no platform given anything but the TPU is refused.

Usage: python scripts/bench_int8.py [--m 8 128 1024] [--trials 5]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

SHAPES = [  # (K, N) of the 7B projections
    ("qkv_o", 4096, 4096),
    ("mlp_up", 4096, 11008),
    ("mlp_down", 11008, 4096),
]


def _best_of(fn, trials: int) -> float:
    from bench import _sync

    _sync(fn())  # compile + warm
    best = np.inf
    for _ in range(trials):
        t0 = time.perf_counter()
        _sync(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--m", type=int, nargs="+", default=[8, 128, 1024])
    ap.add_argument("--trials", type=int, default=5)
    ap.add_argument("--tiny", action="store_true",
                    help="small shapes (CPU interpret-mode smoke: 7B-dims "
                         "interpret runs take many minutes)")
    args = ap.parse_args(argv)
    if args.tiny:
        global SHAPES
        SHAPES = [("tiny_proj", 256, 512)]
        args.m = [min(m, 8) for m in args.m[:1]]
        args.trials = min(args.trials, 2)

    import jax
    import jax.numpy as jnp

    from deepdfa_tpu.llm.quant import _quantize
    from deepdfa_tpu.ops.int8_matmul import int8_matmul

    from bench import start_on_device

    backend, device_kind = start_on_device()
    interpret = backend != "tpu"
    rng = np.random.default_rng(0)
    rows = []
    for name, K, N in SHAPES:
        w = jnp.asarray(rng.normal(size=(K, N)) * 0.02, jnp.float32)
        leaf = _quantize(w)
        w_bf16 = w.astype(jnp.bfloat16)
        for M in args.m:
            x = jnp.asarray(rng.normal(size=(M, K)), jnp.bfloat16)

            int8_fused = jax.jit(
                lambda x, q, s: jnp.sum(
                    int8_matmul(x, q, s, interpret=interpret).astype(jnp.float32)
                )
            )
            t_int8 = _best_of(
                lambda: int8_fused(x, leaf.q, leaf.scale), args.trials
            )
            bf16 = jax.jit(lambda x, w: jnp.sum((x @ w).astype(jnp.float32)))
            t_bf16 = _best_of(lambda: bf16(x, w_bf16), args.trials)
            deq = jax.jit(
                lambda x, q, s: jnp.sum(
                    (x @ (q.astype(jnp.bfloat16) * s.astype(jnp.bfloat16)))
                    .astype(jnp.float32)
                )
            )
            t_deq = _best_of(lambda: deq(x, leaf.q, leaf.scale), args.trials)
            rows.append(
                {
                    "shape": f"{name}_{M}x{K}x{N}",
                    "pallas_int8_ms": round(t_int8 * 1e3, 3),
                    "xla_bf16_ms": round(t_bf16 * 1e3, 3),
                    "xla_dequant_ms": round(t_deq * 1e3, 3),
                    "int8_vs_bf16": round(t_bf16 / t_int8, 2),
                }
            )
    result = {
        "metric": "int8_matmul_microbench",
        "backend": backend,
        "device_kind": device_kind,
        "interpret_mode": interpret,
        "note": ("interpret mode: correctness only, timings meaningless"
                 if interpret else
                 "int8_vs_bf16 > 1 means the fused kernel beats XLA bf16"),
        "rows": rows,
    }
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
