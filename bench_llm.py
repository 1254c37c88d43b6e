"""LLM fine-tune benchmark: **tokens/sec/chip** for CodeLlama-7B-shaped LoRA
training (the north-star metric BASELINE.json names; reference anchor: the
MSIVD HF-Trainer fine-tune loop, ``MSIVD/msivd/train.py:873-911``).

Prints ONE JSON line. Protocol:

- **Default: the FULL 32-layer stack, measured — not extrapolated.** The
  frozen base is **int8-resident** (``int8_runtime=True``: fused
  dequant-matmul pallas kernel with a custom VJP so activation grads flow
  through it, ``ops/int8_matmul.py``), which is the TPU-native analogue of
  the reference's QLoRA setup (4-bit NF4 frozen base + LoRA adapters,
  ``train.py:873-885``) and drops weight HBM from ~13.5 GB to ~6.8 GB — the
  whole 32-layer model plus remat'd training activations fits one v5e, so
  the headline is a measured full-model number. ``--base bf16`` restores the
  previous protocol (bf16 base, ``--layers`` few, per-layer-marginal
  extrapolation to 32).
- LoRA rank 16 on q/v, base weights frozen; causal-LM loss, grads on LoRA
  params only. On OOM the batch halves and retries (recorded as
  ``batch_autotuned`` — a one-shot TPU window must not die on a memory
  guess).
- Headline timing is the **chained protocol** shared with ``bench.py``: one
  jitted ``lax.scan`` over ``--chain`` optimizer steps whose scalar readback
  depends on every step, amortising the per-dispatch round trip. The
  strict single-dispatch number is reported in bf16 mode only (the second
  multi-minute compile is not worth it at 32 layers).
- Self-validation: compiled-step FLOPs from ``cost_analysis`` (a scan body
  is counted ONCE regardless of trip count, so the chained computation's
  number IS the per-step FLOPs), an in-process chained-matmul roofline,
  implied TFLOP/s and MFU; any number over the roofline is REFUSED
  (reported null with the reason).

Usage: python bench_llm.py                 # full 32-layer int8-base, measured
       python bench_llm.py --base bf16 --layers 2   # legacy extrapolation
       JAX_PLATFORMS=cpu python bench_llm.py --tiny   # toy-size rehearsal, labelled cpu
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from bench import (  # shared protocol
    _cost_flops,
    _git_rev,
    _progress,
    _sync,
    _time_once,
    _timed,
    measure_roofline,
    start_on_device,
)

FULL_LAYERS = 32  # CodeLlama-7B


def build_step(cfg, batch: int, seq: int, seed: int = 0, measure_strict: bool = True):
    """(run_once, make_chained, flops, params_info): one jitted LoRA train
    step — causal-LM loss, grads/updates on the LoRA adapters only — plus a
    factory for the chained k-step variant. With ``measure_strict=False`` the
    single-dispatch step is neither warmed nor cost-analysed (two discarded
    multi-minute 7B-dims compiles otherwise): ``run_once``/``flops`` come
    back None and only the chained path compiles; per-step FLOPs then come
    from the chained computation itself (scan body counted once)."""
    import jax
    import jax.numpy as jnp
    import optax

    from deepdfa_tpu.llm.llama import LlamaForCausalLM
    from deepdfa_tpu.llm.lora import split_lora

    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(3, cfg.vocab_size, (batch, seq)), jnp.int32)

    params = jax.jit(lambda: model.init(jax.random.key(0), ids)["params"])()
    # Frozen base as in PEFT: differentiate ONLY the LoRA subtree, so XLA
    # never emits base weight-grad matmuls (activation grads still flow
    # through every layer into earlier adapters, as they must).
    lora_p, base_p = split_lora(params)
    if cfg.int8_runtime:
        from deepdfa_tpu.llm.quant import randomize_int8_runtime_params

        base_p = randomize_int8_runtime_params(base_p, seed=seed + 7)

    def combine(lora, base):
        return jax.tree.map(
            lambda l, b: b if l is None else l, lora, base,
            is_leaf=lambda x: x is None,
        )

    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(2e-4))
    opt_state = jax.jit(tx.init)(lora_p)

    def loss_fn(lora, base, ids):
        logits = model.apply({"params": combine(lora, base)}, ids)
        # next-token cross entropy (the fine-tune objective's compute shape)
        tgt = ids[:, 1:]
        lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
        nll = -jnp.take_along_axis(lp, tgt[..., None], axis=-1)
        return jnp.mean(nll)

    @jax.jit
    def train_step(lora, base, opt_state, ids):
        loss, grads = jax.value_and_grad(loss_fn)(lora, base, ids)
        updates, opt_state = tx.update(grads, opt_state, lora)
        lora = optax.apply_updates(lora, updates)
        return lora, opt_state, loss

    state = {"lora": lora_p, "opt": opt_state}

    def run_once():
        state["lora"], state["opt"], loss = train_step(
            state["lora"], base_p, state["opt"], ids
        )
        return loss

    def make_chained(k: int):
        """k optimizer steps inside ONE jitted lax.scan whose scalar output
        depends on every step (summed losses + updated-LoRA checksum) — the
        same uncheatable RTT-amortising protocol as bench.py, including
        DISTINCT token batches per step as scan xs so XLA cannot hoist
        loop-invariant work (embedding gather, first frozen projections)
        out of the loop. Returns (timed_once, chained_flops) where
        ``chained_flops()`` cost-analyses the computation actually timed."""
        from jax import lax

        ids_k = jnp.asarray(
            np.random.default_rng(seed + 1).integers(
                3, cfg.vocab_size, (k, batch, seq)
            ),
            jnp.int32,
        )

        @jax.jit
        def chained(lora, base, opt_state, ids_k):
            def body(carry, step_ids):
                lora, opt = carry
                lora, opt, loss = train_step(lora, base, opt, step_ids)
                return (lora, opt), loss

            (lora, _opt), losses = lax.scan(body, (lora, opt_state), ids_k)
            checksum = sum(
                jnp.sum(x.astype(jnp.float32)) for x in jax.tree.leaves(lora)
            )
            return jnp.sum(losses) + 0.0 * checksum

        # ONE compile total: AOT-lower once, time the compiled executable,
        # and read cost_analysis off the same executable (calling the jitted
        # fn then lower().compile() separately would compile the 32-layer
        # chain twice — multi-minute each inside a one-shot TPU window)
        compiled_box: dict = {}

        def _compiled():
            if "c" not in compiled_box:
                compiled_box["c"] = chained.lower(
                    state["lora"], base_p, state["opt"], ids_k
                ).compile()
            return compiled_box["c"]

        def timed_once():
            return _compiled()(state["lora"], base_p, state["opt"], ids_k)

        def chained_flops():
            try:
                ca = _compiled().cost_analysis()
                if isinstance(ca, (list, tuple)):
                    ca = ca[0]
                return float(ca["flops"])
            except Exception:
                return None

        return timed_once, chained_flops

    flops = None
    if measure_strict:
        _sync(run_once())  # compile + warm
        flops = _cost_flops(train_step, state["lora"], base_p, state["opt"], ids)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    n_lora = sum(x.size for x in jax.tree.leaves(lora_p))
    weight_bytes = sum(
        x.nbytes for x in jax.tree.leaves(base_p) if x is not None
    )
    return (run_once if measure_strict else None), make_chained, flops, {
        "n_params": int(n_params), "n_lora_params": int(n_lora),
        "weight_gib": round(weight_bytes / 2**30, 2),
    }


def _is_oom(e: BaseException) -> bool:
    s = str(e)
    return "RESOURCE_EXHAUSTED" in s or "Out of memory" in s or "OOM" in s


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--base", choices=("int8", "bf16"), default="int8",
                    help="frozen-base residency: int8 (full stack measured, "
                    "default) or bf16 (few layers + extrapolation)")
    ap.add_argument("--layers", type=int, default=None,
                    help="decoder layers (default: 32 for --base int8, "
                    "2 for --base bf16)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--chain", type=int, default=8,
                    help="k optimizer steps per chained-scan dispatch (headline)")
    ap.add_argument("--lora-rank", type=int, default=16)
    ap.add_argument("--tiny", action="store_true",
                    help="tiny dims (CPU smoke); full-model extrapolation off")
    args = ap.parse_args()

    import jax

    from deepdfa_tpu.llm.llama import codellama_7b, tiny_llama

    int8_base = args.base == "int8" and not args.tiny
    if args.layers is None:
        args.layers = FULL_LAYERS if int8_base else 2

    if args.tiny:
        mk = lambda n: tiny_llama(num_hidden_layers=n, lora_rank=args.lora_rank,
                                  max_position_embeddings=max(args.seq, 256))
        args.batch, args.seq = min(args.batch, 2), min(args.seq, 128)
        args.layers = min(args.layers, 2)
    else:
        mk = lambda n: codellama_7b(
            num_hidden_layers=n, lora_rank=args.lora_rank, remat=True,
            dtype="bfloat16", int8_runtime=int8_base,
        )

    backend, device_kind = start_on_device()
    _progress("measuring roofline")
    roofline = (measure_roofline(n_chain=4, dim=512) if args.tiny
                else measure_roofline())

    def time_chained(timed_once, k: int, trials: int = 3) -> float:
        """Per-step seconds under the chained protocol (compile, then best
        of ``trials`` full-chain readback-synced walls / k)."""
        _sync(timed_once())  # compile + warm
        return min(
            _time_once(lambda: _sync(timed_once())) for _ in range(trials)
        ) / k

    # Strict single-dispatch measurement only where the extra compile is
    # cheap (bf16 few-layer / tiny modes); the 32-layer path times only the
    # chained computation and cost-analyses that same computation.
    measure_strict = not int8_base
    requested_batch = args.batch
    batch = args.batch
    run_once = make_chained = timed_once = chained_flops = None
    while True:
        try:
            _progress(
                f"building {args.layers}-layer "
                f"{'int8-resident' if int8_base else args.base} LoRA step "
                f"(batch {batch} x seq {args.seq})"
            )
            run_once, make_chained, flops, pinfo = build_step(
                mk(args.layers), batch, args.seq, measure_strict=measure_strict
            )
            timed_once, chained_flops = make_chained(args.chain)
            _progress(f"compiling + warming chained scan (k={args.chain})")
            median_s = time_chained(timed_once, args.chain)
            break
        except Exception as e:
            if _is_oom(e) and batch > 1:
                # drop every closure holding the failed attempt's device
                # buffers (base weights, opt state, ids) BEFORE rebuilding —
                # otherwise the halved retry allocates a second full model
                # next to the first and re-OOMs
                run_once = make_chained = timed_once = chained_flops = None
                import gc

                gc.collect()
                _progress(f"OOM at batch {batch}; retrying at {batch // 2}")
                batch //= 2
                continue
            raise
    if flops is None:
        flops = chained_flops()  # scan body counted once == per-step FLOPs

    strict_s = pipelined_s = None
    if measure_strict and run_once is not None:
        strict_s, pipelined_s = _timed(run_once, args.steps)

    # per-layer marginal (embed/head overhead cancels in the difference) —
    # only needed when the measured stack is shallower than the full model
    slope_s = None
    if not args.tiny and args.layers < FULL_LAYERS:
        half = max(args.layers // 2, 1)
        if half < args.layers:
            _, make_chained_half, _, _ = build_step(
                mk(half), batch, args.seq, measure_strict=False
            )
            timed_half, _ = make_chained_half(args.chain)
            half_s = time_chained(timed_half, args.chain)
            slope_s = (median_s - half_s) / (args.layers - half)

    tokens = batch * args.seq
    tok_per_sec = tokens / median_s
    implied = (flops or 0.0) / median_s
    refused = {}
    if flops and roofline and implied > roofline:
        refused["tokens_per_sec_per_chip"] = (
            f"implied {implied / 1e12:.1f} TFLOP/s > roofline "
            f"{roofline / 1e12:.1f} TFLOP/s"
        )
        tok_per_sec = None

    full_model_measured = (not args.tiny) and args.layers == FULL_LAYERS
    est_full = None
    if full_model_measured:
        est_full = tok_per_sec  # measured, not extrapolated
    elif slope_s is not None and slope_s <= 0:
        refused["est_full_model_tokens_per_sec_per_chip"] = (
            f"non-positive per-layer slope ({(slope_s or 0) * 1e3:.2f} ms) — "
            "timing noise exceeded the half-stack difference; raise --steps"
        )
        slope_s = None
    elif slope_s is not None and tok_per_sec is not None:
        t_full = median_s + slope_s * (FULL_LAYERS - args.layers)
        est_full = tokens / t_full

    result = {
        "metric": "llm_lora_tokens_per_sec_per_chip",
        "value": round(tok_per_sec, 1) if tok_per_sec else None,
        "unit": "tokens/sec/chip",
        "vs_baseline": None,  # the reference publishes no tokens/sec number
        "backend": backend,
        "device_kind": device_kind,
        "model": ("tiny_llama" if args.tiny else
                  f"codellama_7b_dims_{'int8' if int8_base else 'bf16'}_base"),
        "base_residency": "tiny" if args.tiny else args.base,
        "layers_measured": args.layers,
        "full_model_measured": full_model_measured,
        "batch": batch,
        "batch_autotuned": (batch != requested_batch) or None,
        "seq": args.seq,
        "lora_rank": args.lora_rank,
        "n_params": pinfo["n_params"],
        "n_lora_params": pinfo["n_lora_params"],
        "base_weight_gib": pinfo["weight_gib"],
        "timing": (
            f"chained: one jitted scan over k={args.chain} optimizer steps, "
            "scalar readback depends on every step; best of 3"
        ),
        "step_ms": round(median_s * 1e3, 2),
        "strict_step_ms": round(strict_s * 1e3, 2) if strict_s else None,
        "strict_tokens_per_sec": round(tokens / strict_s, 1) if strict_s else None,
        "pipelined_tokens_per_sec": (
            round(tokens / pipelined_s, 1) if pipelined_s else None
        ),
        "flops_per_step": flops,
        "implied_tflops": round(implied / 1e12, 2) if flops else None,
        "roofline_tflops": round(roofline / 1e12, 1),
        "mfu": round(implied / roofline, 4) if (flops and roofline) else None,
        "per_layer_ms": round(slope_s * 1e3, 2) if slope_s is not None else None,
        "est_full_model_tokens_per_sec_per_chip": (
            round(est_full, 1) if est_full else None
        ),
        "extrapolation": (
            "none — full model measured" if full_model_measured else
            f"t({args.layers}) + slope x ({FULL_LAYERS}-{args.layers}) layers"
        ),
        "refused": refused or None,
        "git_rev": _git_rev(),
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
