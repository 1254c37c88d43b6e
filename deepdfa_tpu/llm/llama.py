"""CodeLlama (LLaMA-architecture) in Flax, designed for GSPMD sharding.

Replaces the reference's HF ``AutoModelForSequenceClassification`` /
``LlamaForCausalLM`` usage (``MSIVD/msivd/train.py:871-885``,
``hf_inference.py:86-107``). Key differences, all TPU-motivated:

- **bf16 + sharding instead of 4-bit NF4**: the reference quantizes to fit
  consumer GPUs (``train.py:873-877``); on TPU the memory math is solved by
  sharding weights over ``tp``/``fsdp`` mesh axes, which XLA turns into
  all-gather/reduce-scatter over ICI. Params carry *logical* axis names
  (``nn.with_logical_partitioning``); :func:`mesh_shardings` maps them onto a
  mesh via :data:`LOGICAL_RULES`.
- **ring attention for long sequences**: of ``attn_impl``'s two modes,
  ``"ring"`` shards the sequence over ``sp`` (``ops/ring_attention.py``); the
  reference truncates at ``block_size <= 2048`` (``train.py:199-207``), which
  remains the parity mode (``"full"``: XLA's attention, no stock kernel).
- **no data-dependent control flow**: static shapes, causal mask built from
  ``arange`` comparisons, generation via a fixed-size KV cache — everything
  jits once.

Param tree mirrors HF naming (``model.layers.{i}.self_attn.q_proj`` etc.) so
checkpoint conversion (``deepdfa_tpu/llm/convert.py``) is a transpose-only
rename, no surgery.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepdfa_tpu.llm.layers import RMSNorm, apply_rope, rope_cos_sin
from deepdfa_tpu.ops.ring_attention import full_attention, ring_attention_sharded

__all__ = [
    "LlamaConfig",
    "LlamaModel",
    "LlamaForCausalLM",
    "LOGICAL_RULES",
    "mesh_shardings",
    "codellama_7b",
    "codellama_13b",
    "tiny_llama",
]

# logical param/activation axis -> mesh axis. None = replicated.
LOGICAL_RULES = (
    ("batch", "dp"),
    ("seq", "sp"),
    ("embed", "fsdp"),
    ("heads", "tp"),
    ("kv_heads", "tp"),
    ("mlp", "tp"),
    ("vocab", "tp"),
    ("norm", None),
    # routed-expert decoders (llm/longcat.py, llm/pangu_moe.py): a chip holds a *range* of
    # experts (``experts_held``); the logical name is there for the mesh
    # axis that the expert exchange will bring, and maps to none until then
    ("experts", None),
    ("expert_mlp", None),
    ("latent", None),
    ("router", None),
)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    """Architecture hyperparameters (HF ``LlamaConfig`` field parity where the
    names overlap, so conversion can read an HF ``config.json`` directly)."""

    vocab_size: int = 32016
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    rope_theta: float = 1_000_000.0  # CodeLlama uses 1e6 (vs LLaMA-2's 1e4)
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 16384
    dtype: str = "bfloat16"
    attn_impl: str = "full"  # "full" | "ring"
    remat: bool = False  # rematerialize each decoder layer (memory <-> FLOPs)
    lora_rank: int = 0  # 0 = disabled; >0 adds LoRA to q_proj/v_proj
    lora_alpha: float = 16.0
    # int8-resident projection weights via the fused dequant-matmul pallas
    # kernel (ops/int8_matmul.py): halves weight HBM so 7B fits one v5e.
    # Single-chip inference path — incompatible with a GSPMD mesh.
    int8_runtime: bool = False

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def from_hf_dict(cls, d: dict) -> "LlamaConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})


def codellama_7b(**kw) -> LlamaConfig:
    """codellama/CodeLlama-7b-* shapes (``train.py`` preset #1)."""
    return LlamaConfig(**kw)


def codellama_13b(**kw) -> LlamaConfig:
    """codellama/CodeLlama-13b-* shapes (presets #2-#5)."""
    return LlamaConfig(
        hidden_size=5120,
        intermediate_size=13824,
        num_hidden_layers=40,
        num_attention_heads=40,
        num_key_value_heads=40,
        **kw,
    )


def tiny_llama(**kw) -> LlamaConfig:
    """Test-size config (CI / dryrun)."""
    defaults = dict(
        vocab_size=320,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=256,
        dtype="float32",
    )
    defaults.update(kw)
    return LlamaConfig(**defaults)


class Int8Dense(nn.Module):
    """Inference-only projection with **int8-resident** weights: the fused
    dequant-matmul pallas kernel (``ops/int8_matmul.py``) reads ``q`` (int8)
    and the per-channel ``scale`` straight from HBM and dequantises tiles in
    VMEM — weight footprint and traffic halve vs bf16. Params are produced
    from a trained checkpoint by ``quant.to_int8_runtime_params``; ``init``
    only fixes shapes. Single-chip path (a pallas call is not GSPMD-
    partitionable here); the mesh path stays bf16."""

    features: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        from deepdfa_tpu.ops.int8_matmul import int8_matmul

        q = self.param(
            "q", nn.initializers.zeros_init(), (x.shape[-1], self.features), jnp.int8
        )
        scale = self.param(
            "scale", nn.initializers.ones_init(), (self.features,), jnp.float32
        )
        return int8_matmul(
            x, q, scale,
            out_dtype=jnp.dtype(self.dtype),
            interpret=jax.default_backend() == "cpu",
        )


def _dense(
    features: int, in_axis: str, out_axis: str, dtype, name: str,
    int8: bool = False,
) -> nn.Module:
    if int8:
        return Int8Dense(features, dtype=dtype, name=name)
    return nn.Dense(
        features,
        use_bias=False,
        dtype=dtype,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), (in_axis, out_axis)
        ),
        name=name,
    )


class Attention(nn.Module):
    cfg: LlamaConfig
    mesh: Mesh | None = None

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,
        attn_mask: jnp.ndarray | None,
        positions: jnp.ndarray,
        decode: bool = False,
    ) -> jnp.ndarray:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        h, h_kv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        b, s, _ = x.shape

        q_proj = _dense(h * d, "embed", "heads", dtype, "q_proj", int8=cfg.int8_runtime)
        k_proj = _dense(h_kv * d, "embed", "kv_heads", dtype, "k_proj", int8=cfg.int8_runtime)
        v_proj = _dense(h_kv * d, "embed", "kv_heads", dtype, "v_proj", int8=cfg.int8_runtime)
        o_proj = _dense(cfg.hidden_size, "heads", "embed", dtype, "o_proj", int8=cfg.int8_runtime)

        q = q_proj(x)
        k = k_proj(x)
        v = v_proj(x)
        if cfg.lora_rank > 0:
            from deepdfa_tpu.llm.lora import LoRAAdapter

            q = q + LoRAAdapter(
                h * d, cfg.lora_rank, cfg.lora_alpha, dtype=dtype, name="lora_q"
            )(x)
            v = v + LoRAAdapter(
                h_kv * d, cfg.lora_rank, cfg.lora_alpha, dtype=dtype, name="lora_v"
            )(x)
        q = q.reshape(b, s, h, d)
        k = k.reshape(b, s, h_kv, d)
        v = v.reshape(b, s, h_kv, d)

        cos, sin = rope_cos_sin(positions, d, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

        if decode:
            out = self._decode_attend(q, k, v, positions, attn_mask)
        elif cfg.attn_impl == "ring":
            if self.mesh is None:
                raise ValueError("attn_impl='ring' requires a mesh")
            out = ring_attention_sharded(
                q, k, v, self.mesh, causal=True, kv_mask=attn_mask
            )
        else:
            out = full_attention(q, k, v, causal=True, kv_mask=attn_mask)
        return o_proj(out.reshape(b, s, h * d))

    def _decode_attend(self, q, k, v, positions, attn_mask):
        """Single-token step against a fixed-size KV cache (autoregressive
        generation; static shapes, index-updated cache). ``attn_mask``
        [b, 1] marks the *current* token's validity — False for left-padding
        (MSIVD pads left with eos, ``train.py:196-208``), and the cached
        validity mask keeps those K/V slots masked for all later steps."""
        cfg = self.cfg
        b = q.shape[0]
        max_len = cfg.max_position_embeddings
        cached_k = self.variable(
            "cache",
            "cached_key",
            jnp.zeros,
            (b, max_len, cfg.num_key_value_heads, cfg.head_dim),
            k.dtype,
        )
        cached_v = self.variable(
            "cache",
            "cached_value",
            jnp.zeros,
            (b, max_len, cfg.num_key_value_heads, cfg.head_dim),
            v.dtype,
        )
        cached_valid = self.variable(
            "cache", "cached_valid", jnp.zeros, (b, max_len), jnp.bool_
        )
        pos = positions[:, 0]  # [b] current absolute position
        idx = pos[0]  # uniform within a batch step
        cached_k.value = jax.lax.dynamic_update_slice(
            cached_k.value, k, (0, idx, 0, 0)
        )
        cached_v.value = jax.lax.dynamic_update_slice(
            cached_v.value, v, (0, idx, 0, 0)
        )
        step_valid = (
            jnp.ones((b, 1), jnp.bool_) if attn_mask is None else attn_mask.astype(bool)
        )
        cached_valid.value = jax.lax.dynamic_update_slice(
            cached_valid.value, step_valid, (0, idx)
        )
        kv_mask = cached_valid.value & (jnp.arange(max_len)[None, :] <= idx)
        return full_attention(
            q,
            cached_k.value,
            cached_v.value,
            causal=False,  # cache mask already enforces causality
            kv_mask=kv_mask,
        )


class MLP(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        gate = _dense(cfg.intermediate_size, "embed", "mlp", dtype, "gate_proj", int8=cfg.int8_runtime)
        up = _dense(cfg.intermediate_size, "embed", "mlp", dtype, "up_proj", int8=cfg.int8_runtime)
        down = _dense(cfg.hidden_size, "mlp", "embed", dtype, "down_proj", int8=cfg.int8_runtime)
        return down(nn.silu(gate(x)) * up(x))


class DecoderLayer(nn.Module):
    cfg: LlamaConfig
    mesh: Mesh | None = None

    @nn.compact
    def __call__(self, x, attn_mask, positions, decode=False):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        h = RMSNorm(cfg.rms_norm_eps, dtype=dtype, name="input_layernorm")(x)
        x = x + Attention(cfg, mesh=self.mesh, name="self_attn")(
            h, attn_mask, positions, decode=decode
        )
        h = RMSNorm(cfg.rms_norm_eps, dtype=dtype, name="post_attention_layernorm")(x)
        x = x + MLP(cfg, name="mlp")(h)
        return nn.with_logical_constraint(x, ("batch", "seq", "embed"))


class LlamaModel(nn.Module):
    """Decoder stack -> final-norm hidden states [b, s, hidden] (the MSIVD
    fusion contract: ``LLMModel.forward`` returns last hidden states,
    ``model.py:42-59``)."""

    cfg: LlamaConfig
    mesh: Mesh | None = None

    @nn.compact
    def __call__(
        self,
        input_ids: jnp.ndarray,
        attn_mask: jnp.ndarray | None = None,
        positions: jnp.ndarray | None = None,
        decode: bool = False,
    ) -> jnp.ndarray:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        if cfg.int8_runtime and self.mesh is not None:
            raise ValueError(
                "int8_runtime is the single-chip inference path — the pallas "
                "dequant-matmul is not GSPMD-partitionable; use bf16 + mesh "
                "sharding for multi-chip"
            )
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(input_ids.shape[1]), input_ids.shape
            )
        embed = nn.Embed(
            cfg.vocab_size,
            cfg.hidden_size,
            dtype=dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")
            ),
            name="embed_tokens",
        )
        x = embed(input_ids)
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        layer_cls = DecoderLayer
        if cfg.remat:
            layer_cls = nn.remat(DecoderLayer, static_argnums=(4,))
        for i in range(cfg.num_hidden_layers):
            x = layer_cls(cfg, mesh=self.mesh, name=f"layers_{i}")(
                x, attn_mask, positions, decode
            )
        return RMSNorm(cfg.rms_norm_eps, dtype=dtype, name="norm")(x)


class LlamaForCausalLM(nn.Module):
    """LM head on top (generation utility, parity with the reference's
    ``hf_inference.py`` batch-generation helper)."""

    cfg: LlamaConfig
    mesh: Mesh | None = None

    @nn.compact
    def __call__(self, input_ids, attn_mask=None, positions=None, decode=False):
        hidden = LlamaModel(self.cfg, mesh=self.mesh, name="model")(
            input_ids, attn_mask, positions, decode
        )
        logits = _dense(
            self.cfg.vocab_size, "embed", "vocab", jnp.dtype(self.cfg.dtype),
            "lm_head", int8=self.cfg.int8_runtime,
        )(hidden)
        return logits.astype(jnp.float32)


def mesh_shardings(
    model: nn.Module, mesh: Mesh, example_args: tuple, rules=LOGICAL_RULES
):
    """(param_shardings, abstract_params): NamedShardings for every param,
    derived from the logical annotations without materialising weights."""
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.key(0), *example_args)
    )
    logical_specs = nn.get_partition_spec(abstract)
    mesh_specs = nn.logical_to_mesh(logical_specs, rules)
    shardings = jax.tree.map(
        lambda spec: NamedSharding(mesh, spec if spec is not None else P()),
        mesh_specs,
        is_leaf=lambda x: isinstance(x, P) or x is None,
    )
    return shardings, abstract
