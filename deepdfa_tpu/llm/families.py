"""What an encoder family is — the one place that knows.

A family fixes what differs between the stacks that can drive the fusion head: config and module
classes, weights from a seed and from a local HF checkpoint, the test-size (hermetic) config, whether
the encoder is trained, where the head pools. ``scripts/train_joint.py``, ``JointEngine.from_run_dir``
and ``presets.py`` read it here; ``make_joint_steps`` needs none of it (``train_llm`` is ``trained``).
A further family is one row in :data:`FAMILIES` and one model file, imported on use, never with this module.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
from pathlib import Path
from typing import Any, Callable

import flax.linen as nn
import jax
import numpy as np

__all__ = ["EncoderFamily", "FAMILIES", "build_encoder"]


def _llama_from_seed(llm, key, ids, pad_mask):
    return llm.init(key, ids)["params"]


def _llama_from_checkpoint(ckpt_dir, llm_cfg):
    from deepdfa_tpu.llm.convert import load_hf_checkpoint, load_hf_config

    hf_cfg = load_hf_config(ckpt_dir)
    if llm_cfg is not None:
        # shapes come from the HF config.json; TPU-side knobs stay with the preset/defaults
        knobs = ("lora_rank", "lora_alpha", "attn_impl", "dtype")
        hf_cfg = dataclasses.replace(hf_cfg, **{k: getattr(llm_cfg, k) for k in knobs})
    return hf_cfg, load_hf_checkpoint(ckpt_dir)["model"]


def _roberta_from_seed(llm, key, ids, pad_mask):
    # unbox: these params join the trained tree, where boxed leaves would defeat the no-decay mask (its
    # path check would see the box's 'value' leaf) and diverge from the unboxed HF-checkpoint tree shape
    return nn.meta.unbox(llm.init(key, ids, pad_mask)["params"])


def _roberta_from_checkpoint(ckpt_dir, llm_cfg):
    from deepdfa_tpu.llm.convert import load_torch_state
    from deepdfa_tpu.llm.roberta import RobertaConfig, convert_hf_roberta

    hf_cfg = json.loads((Path(ckpt_dir) / "config.json").read_text())
    return RobertaConfig.from_hf_dict(hf_cfg), convert_hf_roberta(load_torch_state(ckpt_dir))


def _sparse_from_seed(llm, key, ids, pad_mask):
    # the frozen decoders' (routed, state-space). jitted: at published widths the weights (bfloat16) are made on the device, never as float32 on the host
    return nn.meta.unbox(jax.jit(llm.init)(key, ids, pad_mask)["params"])


@dataclasses.dataclass(frozen=True)
class EncoderFamily:
    name: str  # and its model file, deepdfa_tpu/llm/<name>.py, which holds
    config: str  # the config class,
    model: str  # the flax module, (input_ids, pad_mask) -> hidden states,
    tiny: str  # and the test-size config's factory
    pool: str  # FusionModel's: "cls" (first real token; bidirectional) or "last" (causal)
    trained: bool  # JointConfig.train_llm: in the trained tree, and the only kind handed dropout keys
    from_seed: Callable[[Any, Any, Any, Any], Any]  # (llm, key, ids, pad_mask) -> params
    # (HF dir, the config whose TPU-side knobs to keep | None) -> (config, params); None: no converter
    from_checkpoint: Callable[[str, Any], tuple[Any, Any]] | None = None
    tiny_kw: Callable[[int], dict] = lambda block_size: {}  # what `tiny` takes of the block

    def _get(self, attr: str):
        return getattr(importlib.import_module(f"deepdfa_tpu.llm.{self.name}"), attr)

    def classes(self) -> tuple[type, type]:
        return self._get(self.config), self._get(self.model)

    def hermetic(self, block_size: int, vocab_size: int = 2048):
        """The config that runs with no preset and no checkpoint."""
        return self._get(self.tiny)(vocab_size=vocab_size, **self.tiny_kw(block_size))


FAMILIES: dict[str, EncoderFamily] = {
    f.name: f
    for f in [
        # causal, dense: CodeLlama, MSIVD's frozen LLM
        EncoderFamily("llama", "LlamaConfig", "LlamaModel", "tiny_llama", pool="last", trained=False,
                      from_seed=_llama_from_seed, from_checkpoint=_llama_from_checkpoint),
        # bidirectional: CodeBERT, which LineVul fine-tunes end to end in EVERY configuration. Its position
        # table must cover the block (+2: positions start at pad_token_id + 1): built AFTER --block_size
        EncoderFamily("roberta", "RobertaConfig", "RobertaEncoder", "tiny_roberta", pool="cls", trained=True,
                      from_seed=_roberta_from_seed, from_checkpoint=_roberta_from_checkpoint,
                      tiny_kw=lambda block_size: {"max_position_embeddings": block_size + 4}),
        # causal, latent attention + routed experts, frozen; no converter yet
        EncoderFamily("longcat", "LongcatConfig", "LongcatModel", "tiny_longcat", pool="last", trained=False,
                      from_seed=_sparse_from_seed),
        # causal, sandwich norms, latent attention, dense then shared + sigmoid-routed experts, frozen; no converter
        EncoderFamily("pangu_moe", "PanguMoeConfig", "PanguMoeModel", "tiny_pangu_moe", pool="last", trained=False,
                      from_seed=_sparse_from_seed),
        # causal, Mamba-1 selective-scan layers with multi-query attention every few, an MLP in every layer,
        # frozen; no converter
        EncoderFamily("jamba", "JambaConfig", "JambaModel", "tiny_jamba", pool="last", trained=False,
                      from_seed=_sparse_from_seed),
        # causal, grouped-query attention global without RoPE or windowed with it by layer, the router read
        # before attention, ReLU-gated routed experts and nothing beside them, frozen; no converter
        EncoderFamily("smallthinker", "SmallThinkerConfig", "SmallThinkerModel", "tiny_smallthinker", pool="last",
                      trained=False, from_seed=_sparse_from_seed),
        # causal, dense, degree-2 power retention (gated linear attention, a [8256, 128] state a key/value
        # head) in place of softmax attention, identical layers as one scan, frozen; no converter
        EncoderFamily("brumby", "BrumbyConfig", "BrumbyModel", "tiny_brumby", pool="last", trained=False,
                      from_seed=_sparse_from_seed),
        # causal, compressed convolutional attention (attention in a latent, two causal convolutions), an
        # MLP router whose state crosses the layers, top-1 SiLU experts or a skip, frozen; no converter
        EncoderFamily("zaya", "ZayaConfig", "ZayaModel", "tiny_zaya", pool="last", trained=False,
                      from_seed=_sparse_from_seed),
    ]
}


def build_encoder(fam: EncoderFamily, llm_cfg: Any, block_size: int, hf_checkpoint: str | None = None, mesh=None):
    """``(llm, llm_params, tokenizer, llm_cfg)`` of one family, a row of :data:`FAMILIES`.

    Without ``hf_checkpoint`` the weights come from ``key(0)`` at ``llm_cfg`` with a :class:`HashTokenizer`
    over its vocabulary; with it, weights, tokenizer and architecture come from the local HF directory (no
    network) and the config returned is the one the module was built with. ``llm_cfg=None`` is the family's
    own: hermetic from a seed, the checkpoint's untouched from a checkpoint. ``mesh`` builds the module over
    it and places the params by their logical axes.
    """
    if hf_checkpoint is None:
        from deepdfa_tpu.llm.dataset import HashTokenizer

        llm_cfg = fam.hermetic(block_size) if llm_cfg is None else llm_cfg
        tokenizer = HashTokenizer(vocab_size=llm_cfg.vocab_size)
    elif fam.from_checkpoint is None:
        raise ValueError(f"the {fam.name} family has no checkpoint conversion yet: "
                         "it is built from a seed at the preset's widths")
    else:
        from transformers import AutoTokenizer

        llm_cfg, llm_params = fam.from_checkpoint(hf_checkpoint, llm_cfg)
        tokenizer = AutoTokenizer.from_pretrained(hf_checkpoint)
    model_cls = fam.classes()[1]
    llm = model_cls(llm_cfg) if mesh is None else model_cls(llm_cfg, mesh=mesh)
    ids, pad_mask = np.zeros((2, block_size), np.int32), np.ones((2, block_size), bool)
    if hf_checkpoint is None:
        llm_params = fam.from_seed(llm, jax.random.key(0), ids, pad_mask)
    if mesh is not None:
        from deepdfa_tpu.llm.llama import mesh_shardings

        shardings, _ = mesh_shardings(llm, mesh, (ids, pad_mask))
        llm_params = jax.device_put(llm_params, shardings["params"])
    return llm, llm_params, tokenizer, llm_cfg
