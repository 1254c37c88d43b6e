"""Joint-training launch presets.

The five MSIVD launch scripts (``MSIVD/msivd/scripts/*.sh``) as structured
configs (``encoder_family="llama"``), the two LineVul configs of BASELINE
config #3 (``scripts/performance_evaluation.sh:7-9``: LineVul alone and
DeepDFA+LineVul combined, ``encoder_family="roberta"``), and the MSIVD job
with a latent-attention routed-expert decoder frozen in the LLM's place, of
either sparse family (``encoder_family="longcat"`` and ``"pangu_moe"``: one
chip's share of an expert-parallel deployment each, and their test-size
twins), a hybrid state-space decoder whole (``"jamba"``, and its twin), or
a grouped-query decoder of global and windowed layers that holds every one of
its routed experts (``"smallthinker"``: SmallThinker-21BA3B, 12 of its 52
layers, on 8k-token blocks; and its twin), or a dense decoder whose attention is
degree-2 power retention (``"brumby"``: Brumby-14B-Base, 10 of its 40 layers,
on the same 8k-token blocks; and its twin), or a decoder of compressed
convolutional attention and top-1 MLP-routed experts (``"zaya"``: ZAYA1-8B,
20 of its 40 layers, on the same 8k-token blocks; and its twin).
What a family is lives in ``llm/families.py`` (eight today; a further
one is one row there and one model file); a preset's
``llm`` must be its family's config class, checked at construction.
``finetuned`` marks presets that start from a LoRA-finetuned model
(the reference's ``--finetuned_path`` / ``PeftInference`` load path,
``train.py:863-869`` — here: convert HF weights, apply LoRA adapters, see
``deepdfa_tpu/llm/{convert,lora}.py``). Mesh suggestions are TPU-side design
(no reference equivalent — it used ``device_map="balanced"``): 7B fits one
v4-8 slice with fsdp; 13B long-block presets shard seq over ``sp`` with ring
attention; a routed decoder holds one chip's range of experts
(``experts_held``).
"""

from __future__ import annotations

import dataclasses

from deepdfa_tpu.config import MeshConfig
from deepdfa_tpu.llm.families import FAMILIES
from deepdfa_tpu.llm.brumby import BrumbyConfig, brumby_14b, tiny_brumby
from deepdfa_tpu.llm.jamba import JambaConfig, jamba2_3b, tiny_jamba
from deepdfa_tpu.llm.joint import JointConfig
from deepdfa_tpu.llm.llama import LlamaConfig, codellama_7b, codellama_13b
from deepdfa_tpu.llm.longcat import LongcatConfig, longcat_flash, tiny_longcat
from deepdfa_tpu.llm.pangu_moe import PanguMoeConfig, openpangu_ultra_moe, tiny_pangu_moe
from deepdfa_tpu.llm.roberta import RobertaConfig, codebert_base
from deepdfa_tpu.llm.smallthinker import SmallThinkerConfig, smallthinker_21b, tiny_smallthinker
from deepdfa_tpu.llm.zaya import ZayaConfig, tiny_zaya, zaya1_8b

__all__ = ["JointPreset", "PRESETS"]


@dataclasses.dataclass(frozen=True)
class JointPreset:
    name: str
    # encoder_family's class
    llm: (LlamaConfig | RobertaConfig | LongcatConfig | PanguMoeConfig | JambaConfig | SmallThinkerConfig
          | BrumbyConfig | ZayaConfig)
    joint: JointConfig
    finetuned: bool  # load LoRA-finetuned weights first (--finetuned_path)
    mesh: MeshConfig
    dataset: str  # reference data family the preset targets
    # which encoder stack drives the fusion head: "llama" (causal, MSIVD),
    # "roberta" (bidirectional CodeBERT — the LineVul configs), "longcat" or
    # "pangu_moe" (causal, latent attention + routed experts, frozen), "jamba"
    # (causal, selective-scan layers + multi-query attention, frozen),
    # "smallthinker" (causal, global / windowed grouped-query attention +
    # routed experts all held, frozen), "brumby" (causal, degree-2 power
    # retention + dense MLPs, frozen), "zaya" (causal, compressed
    # convolutional attention + top-1 MLP-routed experts all held, frozen)
    encoder_family: str = "llama"

    def __post_init__(self):
        config_cls, _ = FAMILIES[self.encoder_family].classes()
        if not isinstance(self.llm, config_cls):  # else LlamaModel(RobertaConfig) or vice versa
            raise TypeError(f"preset {self.name!r}: encoder_family={self.encoder_family!r} builds "
                            f"from a {config_cls.__name__}, not a {type(self.llm).__name__}")


PRESETS: dict[str, JointPreset] = {
    p.name: p
    for p in [
        # bigvul_ft_bigvul.sh — CodeLlama-7B finetuned, Big-Vul
        JointPreset(
            name="bigvul_ft_bigvul",
            llm=codellama_7b(),
            joint=JointConfig(
                block_size=256, epochs=5, train_batch_size=4, eval_batch_size=4,
                learning_rate=1e-4, dataset_style="bigvul",
            ),
            finetuned=True,
            mesh=MeshConfig(dp=-1, fsdp=1, tp=1, sp=1),
            dataset="bigvul",
        ),
        # pretrained_bigvul.sh — 13B pretrained, Big-Vul
        JointPreset(
            name="pretrained_bigvul",
            llm=codellama_13b(),
            joint=JointConfig(
                block_size=350, epochs=1, train_batch_size=8, eval_batch_size=8,
                learning_rate=1e-4, dataset_style="bigvul",
            ),
            finetuned=False,
            mesh=MeshConfig(dp=-1, fsdp=2, tp=1, sp=1),
            dataset="bigvul",
        ),
        # pb_ft_pb.sh — 13B + LoRA, PreciseBugs, long blocks
        JointPreset(
            name="pb_ft_pb",
            llm=codellama_13b(lora_rank=16, attn_impl="ring"),
            joint=JointConfig(
                block_size=2048, epochs=1, train_batch_size=4, eval_batch_size=4,
                learning_rate=1e-6, dataset_style="precisebugs",
            ),
            finetuned=True,
            mesh=MeshConfig(dp=1, fsdp=2, tp=1, sp=-1),
            dataset="precisebugs",
        ),
        # pb_ft_pb_noexpl.sh — 13B-Instruct, no GNN
        JointPreset(
            name="pb_ft_pb_noexpl",
            llm=codellama_13b(),
            joint=JointConfig(
                block_size=1024, epochs=3, train_batch_size=6, eval_batch_size=6,
                learning_rate=1e-6, dataset_style="precisebugs", use_gnn=False,
            ),
            finetuned=True,
            mesh=MeshConfig(dp=-1, fsdp=2, tp=1, sp=1),
            dataset="precisebugs",
        ),
        # pretrained_pb.sh — 13B pretrained, no GNN
        JointPreset(
            name="pretrained_pb",
            llm=codellama_13b(),
            joint=JointConfig(
                block_size=1024, epochs=5, train_batch_size=4, eval_batch_size=4,
                learning_rate=1e-5, dataset_style="precisebugs", use_gnn=False,
            ),
            finetuned=False,
            mesh=MeshConfig(dp=-1, fsdp=2, tp=1, sp=1),
            dataset="precisebugs",
        ),
        # BASELINE config #3a — LineVul alone: fine-tuned CodeBERT classifier
        # (msr_train_linevul.sh: block 512, batch 16, lr 2e-5, 10 epochs)
        JointPreset(
            name="linevul",
            llm=codebert_base(),
            joint=JointConfig(
                block_size=512, epochs=10, train_batch_size=16,
                eval_batch_size=16, learning_rate=2e-5, dataset_style="bigvul",
                use_gnn=False, train_llm=True,
            ),
            finetuned=False,
            mesh=MeshConfig(dp=-1, fsdp=1, tp=1, sp=1),
            dataset="bigvul",
            encoder_family="roberta",
        ),
        # BASELINE config #3b — DeepDFA + LineVul fused classifier
        # (msr_train_combined.sh): CodeBERT fine-tuned end-to-end, pretrained
        # GGNN embeddings frozen (main_cli.py:136-145 freeze-transfer), CLS ⊕
        # pooled-graph concat head
        JointPreset(
            name="linevul_fusion",
            llm=codebert_base(),
            joint=JointConfig(
                block_size=512, epochs=10, train_batch_size=16,
                eval_batch_size=16, learning_rate=2e-5, dataset_style="bigvul",
                use_gnn=True, train_llm=True, freeze_gnn=True,
            ),
            finetuned=False,
            mesh=MeshConfig(dp=-1, fsdp=1, tp=1, sp=1),
            dataset="bigvul",
            encoder_family="roberta",
        ),
        # MSIVD's joint classifier (pb_ft_pb's block 2048 x batch 4, lr 1e-6,
        # GGNN + head trained) over a frozen latent-attention routed-expert
        # decoder at its published widths: rank 0 of the 32 chips that share
        # each layer by expert parallelism (16 of 512 routed experts, all 256
        # zero-compute experts, attention and dense FFNs whole), four layers
        # as one pipeline stage, an eighth of the vocabulary
        JointPreset(
            name="longcat_flash_msivd",
            llm=longcat_flash(num_layers=4, vocab_size=16384, experts_held=(0, 16)),
            joint=JointConfig(
                block_size=2048, epochs=1, train_batch_size=4, eval_batch_size=4,
                learning_rate=1e-6, dataset_style="precisebugs",
            ),
            finetuned=False,
            mesh=MeshConfig(dp=-1, fsdp=1, tp=1, sp=1),
            dataset="precisebugs",
            encoder_family="longcat",
        ),
        # the same code at test size (CPU): 2 of 8 routed experts held
        JointPreset(
            name="tiny_longcat_msivd",
            llm=tiny_longcat(vocab_size=2048, experts_held=(0, 2)),
            joint=JointConfig(
                block_size=64, epochs=1, train_batch_size=4, eval_batch_size=4,
                learning_rate=1e-4, dataset_style="bigvul",
            ),
            finetuned=False,
            mesh=MeshConfig(dp=-1, fsdp=1, tp=1, sp=1),
            dataset="bigvul",
            encoder_family="longcat",
        ),
        # the same job over the other sparse decoder, openPangu-Ultra-MoE at
        # its published widths: rank 0 of the 16 chips that share each layer
        # by expert parallelism (16 of 256 routed experts; attention, the
        # shared expert, the dense FFN and the router whole), one leading
        # dense layer + four expert layers as one pipeline stage, an eighth
        # of the vocabulary
        JointPreset(
            name="openpangu_ultra_msivd",
            llm=openpangu_ultra_moe(num_hidden_layers=5, first_k_dense_replace=1,
                                    vocab_size=19200, experts_held=(0, 16)),
            joint=JointConfig(
                block_size=2048, epochs=1, train_batch_size=4, eval_batch_size=4,
                learning_rate=1e-6, dataset_style="precisebugs",
            ),
            finetuned=False,
            mesh=MeshConfig(dp=-1, fsdp=1, tp=1, sp=1),
            dataset="precisebugs",
            encoder_family="pangu_moe",
        ),
        # the same code at test size (CPU): 2 of 8 routed experts held
        JointPreset(
            name="tiny_pangu_moe_msivd",
            llm=tiny_pangu_moe(vocab_size=2048, experts_held=(0, 2)),
            joint=JointConfig(
                block_size=64, epochs=1, train_batch_size=4, eval_batch_size=4,
                learning_rate=1e-4, dataset_style="bigvul",
            ),
            finetuned=False,
            mesh=MeshConfig(dp=-1, fsdp=1, tp=1, sp=1),
            dataset="bigvul",
            encoder_family="pangu_moe",
        ),
        # the same job over a hybrid state-space decoder, AI21-Jamba2-3B whole:
        # all 28 layers (26 Mamba-1 selective scans, multi-query attention at
        # layers 7 and 21), the whole vocabulary, every width as published —
        # 6.06 GB of bfloat16 weights, nothing cut and no deployment share
        JointPreset(
            name="jamba2_3b_msivd",
            llm=jamba2_3b(),
            joint=JointConfig(
                block_size=2048, epochs=1, train_batch_size=4, eval_batch_size=4,
                learning_rate=1e-6, dataset_style="precisebugs",
            ),
            finetuned=False,
            mesh=MeshConfig(dp=-1, fsdp=1, tp=1, sp=1),
            dataset="precisebugs",
            encoder_family="jamba",
        ),
        # the same code at test size (CPU): 8 layers, attention at 2 and 6
        JointPreset(
            name="tiny_jamba_msivd",
            llm=tiny_jamba(vocab_size=2048),
            joint=JointConfig(
                block_size=64, epochs=1, train_batch_size=4, eval_batch_size=4,
                learning_rate=1e-4, dataset_style="bigvul",
            ),
            finetuned=False,
            mesh=MeshConfig(dp=-1, fsdp=1, tp=1, sp=1),
            dataset="bigvul",
            encoder_family="jamba",
        ),
        # the same job on 8k-token inputs (a function with its callers' and
        # callees' bodies and the explanation) over SmallThinker-21BA3B-Instruct
        # at its published widths: three periods of its layer pattern (a global
        # layer without RoPE, then three with the 4096-token window and RoPE)
        # as one pipeline stage, ALL 64 routed experts of each layer on this
        # chip (no layer is divided), the whole vocabulary
        JointPreset(
            name="smallthinker_21b_msivd",
            llm=smallthinker_21b(num_hidden_layers=12, experts_held=(0, 64)),
            joint=JointConfig(
                block_size=8192, epochs=1, train_batch_size=2, eval_batch_size=2,
                learning_rate=1e-6, dataset_style="precisebugs",
            ),
            finetuned=False,
            mesh=MeshConfig(dp=-1, fsdp=1, tp=1, sp=1),
            dataset="precisebugs",
            encoder_family="smallthinker",
        ),
        # the same code at test size (CPU): 8 layers, global at 0 and 4, a
        # window of 24 in a block of 64, all 8 experts held
        JointPreset(
            name="tiny_smallthinker_msivd",
            llm=tiny_smallthinker(vocab_size=2048),
            joint=JointConfig(
                block_size=64, epochs=1, train_batch_size=4, eval_batch_size=4,
                learning_rate=1e-4, dataset_style="bigvul",
            ),
            finetuned=False,
            mesh=MeshConfig(dp=-1, fsdp=1, tp=1, sp=1),
            dataset="bigvul",
            encoder_family="smallthinker",
        ),
        # the same job on the same 8k-token inputs over Brumby-14B-Base at its
        # published widths: 10 of its 40 identical layers (degree-2 power
        # retention, 40 query heads over 8 key/value heads of 128, a 17,408-wide
        # MLP) as one stage of a four-stage pipeline, no layer divided, the
        # whole vocabulary: 8.16 GB of bfloat16 weights
        JointPreset(
            name="brumby_14b_msivd",
            llm=brumby_14b(num_hidden_layers=10),
            joint=JointConfig(
                block_size=8192, epochs=1, train_batch_size=2, eval_batch_size=2,
                learning_rate=1e-6, dataset_style="precisebugs",
            ),
            finetuned=False,
            mesh=MeshConfig(dp=-1, fsdp=1, tp=1, sp=1),
            dataset="precisebugs",
            encoder_family="brumby",
        ),
        # the same code at test size (CPU): 2 layers, chunks of 16 in a block of 64
        JointPreset(
            name="tiny_brumby_msivd",
            llm=tiny_brumby(vocab_size=2048),
            joint=JointConfig(
                block_size=64, epochs=1, train_batch_size=4, eval_batch_size=4,
                learning_rate=1e-4, dataset_style="bigvul",
            ),
            finetuned=False,
            mesh=MeshConfig(dp=-1, fsdp=1, tp=1, sp=1),
            dataset="bigvul",
            encoder_family="brumby",
        ),
        # the same job on the same 8k-token inputs over ZAYA1-8B at its
        # published widths: 20 of its 40 layers (compressed convolutional
        # attention, 8 query heads over 2 key/value heads of 128 in a latent;
        # an MLP router carrying its state across layers; top-1 of 16 SiLU
        # experts 2,048 wide or a skip) as one stage of a two-stage pipeline,
        # ALL 16 experts of each layer on this chip (no layer is divided), the
        # whole tied vocabulary: 9.40 GB of bfloat16 weights
        JointPreset(
            name="zaya1_8b_msivd",
            llm=zaya1_8b(num_hidden_layers=20, experts_held=(0, 16)),
            joint=JointConfig(
                block_size=8192, epochs=1, train_batch_size=2, eval_batch_size=2,
                learning_rate=1e-6, dataset_style="precisebugs",
            ),
            finetuned=False,
            mesh=MeshConfig(dp=-1, fsdp=1, tp=1, sp=1),
            dataset="precisebugs",
            encoder_family="zaya",
        ),
        # the same code at test size (CPU): 4 layers, 8 experts and the skip
        JointPreset(
            name="tiny_zaya_msivd",
            llm=tiny_zaya(vocab_size=2048),
            joint=JointConfig(
                block_size=64, epochs=1, train_batch_size=4, eval_batch_size=4,
                learning_rate=1e-4, dataset_style="bigvul",
            ),
            finetuned=False,
            mesh=MeshConfig(dp=-1, fsdp=1, tp=1, sp=1),
            dataset="bigvul",
            encoder_family="zaya",
        ),
    ]
}
