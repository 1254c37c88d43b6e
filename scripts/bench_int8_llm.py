"""Full-model int8-resident LLM inference bench: MEASURED, not extrapolated.

CodeLlama-7B in bf16 (~13.5 GB of weights) barely fits one v5e, so
``bench_llm.py`` measures a few layers and extrapolates. With
``int8_runtime=True`` every projection is int8-resident (~6.8 GB at 7B dims
— fused dequant-matmul pallas kernel, ``ops/int8_matmul.py``), and the FULL
32-layer stack fits a single chip with headroom: this script times the whole
model end to end and prints ONE self-validating JSON line —
``int8_resident_tokens_per_sec_per_chip`` at ``--layers 32`` (default).

Params are initialised DIRECTLY in int8 on device (``Int8Dense.init``
creates int8 zero tensors; no f32 materialisation that would OOM at 7B),
then randomised in place: int8 weights uniform in [-127, 127], per-channel
scales ~N(1,0.1)·1e-2, bf16 embeddings ~N(0, 0.02) — the kernel does
identical work regardless of values, and nonzero data keeps the
logits-finiteness check meaningful.

Protocol shared with ``bench.py``/``bench_llm.py``: headline = chained
``lax.scan`` over k distinct token batches whose scalar readback depends on
every step; FLOPs from ``cost_analysis``; implied FLOP/s refused if over the
in-process matmul roofline (the kernel dequantises to bf16 tiles before its
MACs, so the bf16 ceiling applies). Reference anchor: the 4-bit NF4
inference assembly this replaces, ``MSIVD/msivd/train.py:873-885`` /
``hf_inference.py:86-107``.

``--decode N`` switches to the autoregressive DECODE benchmark: the same
int8-resident full stack behind a fixed-size KV cache, one ``lax.scan``
over single-token steps (``llm/generate.py``) — the weights-bandwidth
regime interactive generation lives in (each step re-reads every weight at
small batch), vs the compute-shaped prefill forward the default measures.

Usage: python scripts/bench_int8_llm.py [--layers 32] [--batch 4]
       [--seq 1024] [--chain 8] [--tiny]
       python scripts/bench_int8_llm.py --decode 128 --batch 8
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import (  # noqa: E402  (shared protocol)
    _cost_flops,
    _git_rev,
    _progress,
    _sync,
    _time_once,
    measure_roofline,
    start_on_device,
)

FULL_LAYERS = 32  # CodeLlama-7B


def bench_decode(model, cfg, params, args, roofline, backend, device_kind):
    """Autoregressive DECODE throughput: the full int8-resident stack behind
    a fixed-size KV cache, one ``lax.scan`` over single-token steps (the
    ``llm/generate.py`` loop — the scan is its own chained protocol: the
    returned tokens depend on every step). At batch<<128 each step re-reads
    every weight, so this is the weights-bandwidth regime — the honest
    inference number for interactive generation, vs the prefill-style
    forward the default mode measures. Reference anchor: the batch
    generation helper, ``MSIVD/msivd/hf_inference.py:129-162``."""
    import jax
    import jax.numpy as jnp

    from deepdfa_tpu.llm.generate import GenerateConfig, generate

    rng = np.random.default_rng(2)
    b, s = args.batch, args.decode_prompt
    ids = np.asarray(rng.integers(3, cfg.vocab_size, (b, s)), np.int32)
    pad = np.ones((b, s), bool)
    gcfg = GenerateConfig(max_new_tokens=args.decode, temperature=0.0,
                          eos_token_id=-1)  # greedy, never stops early

    _progress(f"compiling + warming decode scan (b={b}, prompt {s}, "
              f"new {args.decode})")
    out = generate(model, params, ids, pad, gcfg)  # compile + warm
    assert out.shape == (b, args.decode)
    t = min(
        _time_once(lambda: np.asarray(generate(model, params, ids, pad, gcfg)))
        for _ in range(3)
    )
    # every scan step is one single-token forward (prompt teacher-forcing
    # steps cost the same as sampled steps)
    steps = s + args.decode - 1
    tok_per_sec = b * steps / t
    result = {
        "metric": "int8_resident_decode_tokens_per_sec_per_chip",
        "value": round(tok_per_sec, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": None,
        "backend": backend,
        "device_kind": device_kind,
        "model": "tiny_llama" if args.tiny else "codellama_7b_dims",
        "layers": cfg.num_hidden_layers,
        "batch": b,
        "prompt_len": s,
        "new_tokens": args.decode,
        "kv_cache_len": cfg.max_position_embeddings,
        "step_ms": round(t / steps * 1e3, 3),
        "timing": ("one jitted lax.scan over all single-token steps; "
                   "returned tokens depend on every step; best of 3"),
        "regime": ("weights-bandwidth-bound at small batch: each step "
                   "re-reads the int8-resident weights"),
        "roofline_tflops": round(roofline / 1e12, 1),
        "git_rev": _git_rev(),
    }
    print(json.dumps(result))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=FULL_LAYERS)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--chain", type=int, default=8)
    ap.add_argument("--decode", type=int, default=0, metavar="NEW_TOKENS",
                    help="measure autoregressive decode throughput instead "
                    "of the prefill-style forward")
    ap.add_argument("--decode-prompt", type=int, default=16)
    ap.add_argument("--tiny", action="store_true", help="tiny dims (CPU smoke)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax

    from deepdfa_tpu.llm.llama import LlamaForCausalLM, codellama_7b, tiny_llama

    if args.tiny:
        cfg = tiny_llama(int8_runtime=True, max_position_embeddings=max(args.seq, 256))
        args.batch, args.seq = min(args.batch, 2), min(args.seq, 128)
        args.layers = cfg.num_hidden_layers  # report the real tiny depth
    else:
        # decode mode caps the KV cache at prompt+new (the default 16384
        # max_position_embeddings would allocate an ~8.6 GB/batch-row cache)
        max_pos = (
            -(-(args.decode_prompt + args.decode) // 128) * 128
            if args.decode else 16384
        )
        cfg = codellama_7b(num_hidden_layers=args.layers, int8_runtime=True,
                           dtype="bfloat16", max_position_embeddings=max_pos)

    backend, device_kind = start_on_device()
    _progress("measuring roofline")
    roofline = (measure_roofline(n_chain=4, dim=512) if args.tiny
                else measure_roofline())

    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(3, cfg.vocab_size, (args.batch, args.seq)),
                      jnp.int32)
    _progress(f"initialising int8-resident params ({args.layers} layers) on device")
    from deepdfa_tpu.llm.quant import randomize_int8_runtime_params

    params = jax.jit(lambda: model.init(jax.random.key(0), ids)["params"])()
    params = randomize_int8_runtime_params(params, seed=1)
    # leaf.nbytes sums device metadata — tree_nbytes would pull ~6.8 GB of
    # weights back to the host just to count them
    weight_bytes = sum(l.nbytes for l in jax.tree.leaves(params))

    if args.decode:
        return bench_decode(model, cfg, params, args, roofline, backend,
                            device_kind)

    fwd = lambda p, i: model.apply({"params": p}, i)
    ids_k = jnp.asarray(
        rng.integers(3, cfg.vocab_size, (args.chain, args.batch, args.seq)),
        jnp.int32,
    )

    @jax.jit
    def chained(params, ids_k):
        def body(acc, step_ids):
            logits = fwd(params, step_ids)
            # checksum over EVERY logit position: a last-position slice would
            # let XLA skip the lm_head matmul for seq-1 positions while FLOPs
            # were counted for all of them
            return acc + jnp.sum(logits.astype(jnp.float32)), None

        acc, _ = lax.scan(body, jnp.zeros((), jnp.float32), ids_k)
        return acc

    _progress(f"compiling + warming chained scan (k={args.chain})")
    check = _sync(chained(params, ids_k))
    assert np.isfinite(check), f"non-finite logits checksum: {check}"
    # FLOPs from the ONE computation actually timed: no discarded multi-
    # minute jit(fwd) compile at 7B dims, and no counted-vs-executed
    # mismatch. cost_analysis counts a scan body ONCE regardless of trip
    # count (verified: constant across k=2/4/8), so the chain's number IS
    # the per-step FLOPs — dividing by k would under-report k× and neuter
    # the roofline gate.
    flops = _cost_flops(chained, params, ids_k)
    wall = min(_time_once(lambda: _sync(chained(params, ids_k))) for _ in range(3))
    step_s = wall / args.chain

    tokens = args.batch * args.seq
    tok_per_sec = tokens / step_s
    implied = (flops or 0.0) / step_s
    refused = None
    if flops and roofline and implied > roofline:
        refused = (f"implied {implied / 1e12:.1f} TFLOP/s > roofline "
                   f"{roofline / 1e12:.1f} TFLOP/s")
        tok_per_sec = None

    result = {
        "metric": "int8_resident_tokens_per_sec_per_chip",
        "value": round(tok_per_sec, 1) if tok_per_sec else None,
        "unit": "tokens/sec/chip",
        "vs_baseline": None,  # reference publishes no NF4 throughput number
        "backend": backend,
        "device_kind": device_kind,
        "model": "tiny_llama" if args.tiny else "codellama_7b_dims",
        "layers": args.layers,
        "full_model_measured": (not args.tiny) and args.layers == FULL_LAYERS,
        "batch": args.batch,
        "seq": args.seq,
        "weight_gib": round(weight_bytes / 2**30, 2),
        "timing": (f"chained: one jitted scan over k={args.chain} forwards, "
                   "scalar readback depends on every step; best of 3"),
        "step_ms": round(step_s * 1e3, 2),
        "flops_per_step": flops,
        "implied_tflops": round(implied / 1e12, 2) if flops else None,
        "roofline_tflops": round(roofline / 1e12, 1),
        "mfu": round(implied / roofline, 4) if (flops and roofline) else None,
        "refused": refused,
        "git_rev": _git_rev(),
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
