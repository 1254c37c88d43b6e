"""LLM layer: TPU-native replacement of the reference's MSIVD subsystem
(``MSIVD/msivd/`` — CodeLlama + DDFA-GGNN fusion for vulnerability detection).

Where the reference leans on CUDA-only machinery — bitsandbytes 4-bit NF4
quantization (``train.py:873-885``), HF accelerate ``device_map`` layer
placement (``train.py:883``), ``torch.nn.DataParallel`` (``train.py:936``) —
this package uses bf16 weights GSPMD-sharded over a named mesh (tp/fsdp for
weights, dp for batch, sp + ring attention for long sequences; a routed
decoder is told the range of experts its chip holds). Eight encoder families
drive the fusion head:
``llama`` (causal, dense), ``roberta`` (bidirectional), ``longcat`` and
``pangu_moe`` (causal, latent attention, routed experts), ``jamba`` (causal,
selective-scan layers with multi-query attention every few), ``smallthinker``
(grouped-query attention, global and windowed, routed experts), ``brumby``
(power retention) and ``zaya`` (compressed convolutional attention, top-1
MLP-routed experts): what a family is lives in ``families.py``. A further one is
one row there and one model file, which builds from ``layers.py`` and asks
``ops/dispatch.py`` whether its kernel runs.
"""

from deepdfa_tpu.llm.llama import (  # noqa: F401
    LlamaConfig,
    LlamaModel,
    LlamaForCausalLM,
)

__all__ = [
    "LlamaConfig",
    "LlamaModel",
    "LlamaForCausalLM",
    # submodules (imported lazily by callers):
    # convert  — HF checkpoint conversion
    # lora     — adapters, mask/split/merge
    # finetune — LoRA causal-LM tuning stage
    # quant    — int8 weight storage
    # dataset  — text examples + graph index-join
    # fusion   — classification heads over LLM ⊕ GGNN
    # joint    — frozen-LLM joint trainer
    # generate — batch decoding
    # layers   — what the decoders share, in no family's file: projections,
    #            the dense FFN, RMSNorm, RoPE, the embedding, an expert layer's
    #            pad mask and counts, and the one form of the ``stats`` channel
    # roberta  — bidirectional encoder (CodeBERT, the LineVul configurations)
    # longcat  — latent attention + routed experts on a shortcut, zero-compute
    #            experts; holds a range of experts (frozen decoder of the
    #            joint classifier); its latent attention and held experts
    #            serve pangu_moe and smallthinker too
    # pangu_moe — sandwich norms, latent attention, leading dense layers then a
    #            shared expert beside sigmoid-routed experts (frozen decoder too)
    # jamba    — Mamba-1 selective-scan layers (ops/selective_scan.py) with
    #            multi-query attention every few, an MLP in every layer (frozen
    #            decoder too, whole)
    # smallthinker — grouped-query attention (ops/gqa_attention.py), global
    #            layers beside windowed ones with RoPE, a router read before
    #            attention, ReLU-gated experts (frozen decoder too)
    # brumby   — degree-2 power retention (ops/power_retention.py) in a
    #            scanned stack of dense layers (frozen decoder too)
    # zaya     — compressed convolutional attention (ops/cca.py, then
    #            ops/gqa_attention.py), an MLP router whose state crosses the
    #            layers, top-1 SiLU experts or a skip (frozen decoder too;
    #            preset zaya1_8b_msivd)
    # families — what an encoder family is, and build_encoder over it: classes,
    #            weights, tokenizer, pooling, trained or frozen
    # presets  — the launch configurations: five MSIVD scripts (llama), two
    #            LineVul (roberta), a frozen decoder and its tiny twin each for
    #            longcat, pangu_moe, jamba, smallthinker, brumby and zaya
]
