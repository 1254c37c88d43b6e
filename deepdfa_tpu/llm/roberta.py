"""RoBERTa-family bidirectional encoder (CodeBERT) in Flax — the LineVul side
of BASELINE config #3 ("DeepDFA + LineVul fused classifier").

The reference's third evaluation config trains LineVul — a CodeBERT
(`microsoft/codebert-base`, RoBERTa-base architecture) sequence classifier —
and then the combination, where DeepDFA's pooled GGNN embedding is
concatenated with the CLS vector before the classification head
(``scripts/performance_evaluation.sh:7-9``; the LineVul tree itself is not
vendored in the reference snapshot, so the contract here is the public
LineVul/CodeBERT architecture plus the reference's freeze-transfer hook,
``DDFA/code_gnn/main_cli.py:136-145``).

TPU design notes (vs a torch translation):

- bidirectional attention, no causal structure, no KV cache. On a TPU it is
  ``ops/flash_attention``: forward and backward kernels that keep a
  ``[block_q, s]`` tile of scores in VMEM (softmax in float32, the tile
  recomputed in the backward), so no ``[b, h, s, s]`` tensor is written to
  HBM or kept for the backward; the pad mask goes in as per-token segment
  ids. The kernels are taken when the process has one TPU device (nothing
  is sharded), ``s`` is a multiple of 128, the heads tile 128 lanes and
  attention dropout draws nothing (``deterministic`` or rate 0.0: a
  fused kernel cannot draw flax's mask). Everywhere else — the CPU, short
  sequences, the published dropout 0.1, meshes — the layer is a single masked
  softmax over the full [s, s] score matrix in XLA ops, as it always was.
  ``RobertaEncoder`` sows ``attn_layers`` / ``attn_fused`` into ``stats`` so
  a step can say which it ran. Sequences are short (LineVul block 512), so
  no ring/sp path is needed; the encoder rides ``dp``/``fsdp``/``tp`` mesh
  axes via the same logical-axis rules as the Llama stack (``llama.py
  LOGICAL_RULES``).
- learned absolute positions (RoBERTa convention: real tokens get
  consecutive positions starting at ``pad_token_id + 1``) are computed from
  the explicit pad mask, so the framework-wide left-pad convention works
  unchanged — position embeddings see the same values as HF's
  right-padded layout, shifted mask-aware.
- the param tree mirrors HF naming (``embeddings.word_embeddings``,
  ``encoder.layer.{i}.attention.self.query`` → ``layer_{i}/attention/self/
  query``), so :func:`convert_hf_roberta` is a rename/transpose, no surgery.

``RobertaEncoder.apply(params, ids, pad_mask)`` returns final hidden states
``[b, s, h]`` — the same contract as :class:`~deepdfa_tpu.llm.llama.LlamaModel`,
so the joint trainer drives either stack.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepdfa_tpu.llm.layers import sow_stats
from deepdfa_tpu.ops.dispatch import kernel_mode

__all__ = [
    "RobertaConfig",
    "RobertaEncoder",
    "codebert_base",
    "tiny_roberta",
    "convert_hf_roberta",
    "roberta_position_ids",
]


@dataclasses.dataclass(frozen=True)
class RobertaConfig:
    """HF ``RobertaConfig`` field parity where names overlap (so an HF
    ``config.json`` loads directly via :meth:`from_hf_dict`)."""

    vocab_size: int = 50265
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 514
    type_vocab_size: int = 1
    layer_norm_eps: float = 1e-5
    pad_token_id: int = 1
    # HF training regularisation (LineVul fine-tunes CodeBERT end-to-end
    # with these at 0.1): applied only when a caller passes
    # ``deterministic=False`` — inference/parity paths are unaffected
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    dtype: str = "float32"  # bfloat16 on TPU; f32 for parity tests

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def from_hf_dict(cls, d: dict) -> "RobertaConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})


def codebert_base(**kw) -> RobertaConfig:
    """microsoft/codebert-base shapes (RoBERTa-base; the LineVul encoder)."""
    return RobertaConfig(**kw)


def tiny_roberta(**kw) -> RobertaConfig:
    """Test-size config (CI / hermetic demo)."""
    defaults = dict(
        vocab_size=320,
        hidden_size=64,
        num_hidden_layers=2,
        num_attention_heads=4,
        intermediate_size=128,
        max_position_embeddings=260,
    )
    defaults.update(kw)
    return RobertaConfig(**defaults)


def roberta_position_ids(pad_mask: jnp.ndarray, pad_token_id: int) -> jnp.ndarray:
    """RoBERTa position ids from the pad mask: real tokens count up from
    ``pad_token_id + 1`` in sequence order, pads sit at ``pad_token_id``
    (HF ``create_position_ids_from_input_ids`` semantics, but driven by the
    explicit mask — pad==eos value-sniffing is the bug the dataset layer
    already refuses to replicate)."""
    m = pad_mask.astype(jnp.int32)
    return jnp.cumsum(m, axis=1) * m + pad_token_id


def _dense(features: int, in_axis: str, out_axis: str, dtype, name: str) -> nn.Module:
    return nn.Dense(
        features,
        use_bias=True,
        dtype=dtype,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), (in_axis, out_axis)
        ),
        bias_init=nn.with_logical_partitioning(
            nn.initializers.zeros_init(), (out_axis,)
        ),
        name=name,
    )


def _layer_norm(eps: float) -> nn.LayerNorm:
    """Post-LN LayerNorm in f32 (BERT-family numerics are LN-sensitive);
    named ``LayerNorm`` so the param path mirrors HF exactly."""
    return nn.LayerNorm(
        epsilon=eps, dtype=jnp.float32, param_dtype=jnp.float32, name="LayerNorm"
    )


def _fused_attention(cfg: RobertaConfig, seq_len: int, deterministic: bool) -> bool | None:
    """The ``interpret`` flag for the attention kernels, or ``None`` where the
    einsum-softmax path has to run: no kernel here, a shape it does not take
    (``ops/dispatch.py``), or an attention dropout that draws a mask."""
    if not deterministic and cfg.attention_probs_dropout_prob != 0.0:
        return None
    return kernel_mode("flash_attention", seq_len, cfg.num_attention_heads, cfg.head_dim)


class _SelfAttention(nn.Module):
    """``attention.self``: Q/K/V projections + bidirectional masked softmax;
    ``fused`` (from :func:`_fused_attention`) is ``None`` for the XLA ops
    below, else the ``interpret`` flag of the kernels that take their place."""

    cfg: RobertaConfig

    @nn.compact
    def __call__(
        self, x: jnp.ndarray, pad_mask: jnp.ndarray | None,
        deterministic: bool = True, fused: bool | None = None,
    ) -> jnp.ndarray:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        b, s, _ = x.shape
        h, d = cfg.num_attention_heads, cfg.head_dim
        proj = lambda name: _dense(h * d, "embed", "heads", dtype, name)(x)
        if fused is not None:
            from deepdfa_tpu.ops.flash_attention import flash_attention

            # real queries see exactly the real keys, as below; pad queries
            # see the pad keys, and nothing reads their rows either way
            segments = (jnp.ones((b, s), jnp.int32) if pad_mask is None
                        else pad_mask.astype(jnp.int32))
            return flash_attention(
                proj("query"), proj("key"), proj("value"), segments,
                num_heads=h, interpret=fused)
        q = proj("query").reshape(b, s, h, d)
        k = proj("key").reshape(b, s, h, d)
        v = proj("value").reshape(b, s, h, d)
        # [b, h, s_q, s_k] scores in f32; pads masked on the key axis only —
        # pad *query* rows produce garbage that downstream pooling never reads
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        scores = scores / np.sqrt(d)
        if pad_mask is not None:
            bias = jnp.where(pad_mask[:, None, None, :], 0.0, -1e9)
            scores = scores + bias
        probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
        probs = nn.Dropout(cfg.attention_probs_dropout_prob,
                           deterministic=deterministic)(probs)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        return out.reshape(b, s, h * d)


class _AttentionBlock(nn.Module):
    """``attention``: self-attention + output projection + residual post-LN."""

    cfg: RobertaConfig

    @nn.compact
    def __call__(
        self, x: jnp.ndarray, pad_mask: jnp.ndarray | None,
        deterministic: bool = True, fused: bool | None = None,
    ) -> jnp.ndarray:
        attn = _SelfAttention(self.cfg, name="self")(x, pad_mask, deterministic, fused)
        # HF nests output.dense + output.LayerNorm under attention.output —
        # the tree shape is attention/{self,output}/...
        return _AttnOutput(self.cfg, name="output")(attn, x, deterministic)


class _AttnOutput(nn.Module):
    cfg: RobertaConfig

    @nn.compact
    def __call__(
        self, attn: jnp.ndarray, residual: jnp.ndarray,
        deterministic: bool = True,
    ) -> jnp.ndarray:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        y = _dense(cfg.hidden_size, "heads", "embed", dtype, "dense")(attn)
        y = nn.Dropout(cfg.hidden_dropout_prob, deterministic=deterministic)(y)
        return _layer_norm(cfg.layer_norm_eps)(y + residual).astype(dtype)


class _FFNOutput(nn.Module):
    cfg: RobertaConfig

    @nn.compact
    def __call__(
        self, ff: jnp.ndarray, residual: jnp.ndarray,
        deterministic: bool = True,
    ) -> jnp.ndarray:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        y = _dense(cfg.hidden_size, "mlp", "embed", dtype, "dense")(ff)
        y = nn.Dropout(cfg.hidden_dropout_prob, deterministic=deterministic)(y)
        return _layer_norm(cfg.layer_norm_eps)(y + residual).astype(dtype)


class _Intermediate(nn.Module):
    cfg: RobertaConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        y = _dense(cfg.intermediate_size, "embed", "mlp", dtype, "dense")(x)
        # HF "gelu" is the exact (erf) form
        return nn.gelu(y, approximate=False)


class RobertaLayer(nn.Module):
    cfg: RobertaConfig

    @nn.compact
    def __call__(
        self, x: jnp.ndarray, pad_mask: jnp.ndarray | None,
        deterministic: bool = True, fused: bool | None = None,
    ) -> jnp.ndarray:
        x = _AttentionBlock(self.cfg, name="attention")(x, pad_mask, deterministic, fused)
        ff = _Intermediate(self.cfg, name="intermediate")(x)
        x = _FFNOutput(self.cfg, name="output")(ff, x, deterministic)
        return nn.with_logical_constraint(x, ("batch", "seq", "embed"))


class _Embeddings(nn.Module):
    cfg: RobertaConfig
    deterministic: bool = True

    @nn.compact
    def __call__(self, input_ids: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)

        def emb(n, name, row_axis="vocab"):
            # only the word table is big enough to shard its rows over tp;
            # position tables can be odd-sized and the token-type table has
            # ONE row (RoBERTa never uses segment B) — those replicate
            return nn.Embed(
                n, cfg.hidden_size, dtype=dtype,
                embedding_init=nn.with_logical_partitioning(
                    nn.initializers.normal(0.02), (row_axis, "embed")
                ),
                name=name,
            )

        x = emb(cfg.vocab_size, "word_embeddings")(input_ids)
        x = x + emb(cfg.max_position_embeddings, "position_embeddings",
                    row_axis=None)(positions)
        # token type 0 everywhere (RoBERTa never uses segment B)
        x = x + emb(cfg.type_vocab_size, "token_type_embeddings",
                    row_axis=None)(jnp.zeros_like(input_ids))
        x = _layer_norm(cfg.layer_norm_eps)(x).astype(dtype)
        return nn.Dropout(cfg.hidden_dropout_prob,
                          deterministic=self.deterministic)(x)


class RobertaEncoder(nn.Module):
    """Embeddings + ``num_hidden_layers`` post-LN blocks → final hidden
    states ``[b, s, h]``. Same apply contract as ``LlamaModel`` so the joint
    trainer and fusion head drive either stack; the CLS read happens in the
    fusion head (``pool="cls"``)."""

    cfg: RobertaConfig

    @nn.compact
    def __call__(
        self,
        input_ids: jnp.ndarray,
        pad_mask: jnp.ndarray | None = None,
        positions: jnp.ndarray | None = None,
        deterministic: bool = True,
    ) -> jnp.ndarray:
        cfg = self.cfg
        if positions is None:
            if pad_mask is None:
                positions = (
                    jnp.broadcast_to(jnp.arange(input_ids.shape[1]), input_ids.shape)
                    + cfg.pad_token_id + 1
                )
            else:
                positions = roberta_position_ids(pad_mask, cfg.pad_token_id)
        x = _Embeddings(cfg, deterministic, name="embeddings")(input_ids, positions)
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        fused = _fused_attention(cfg, input_ids.shape[1], deterministic)
        for i in range(cfg.num_hidden_layers):
            x = RobertaLayer(cfg, name=f"layer_{i}")(x, pad_mask, deterministic, fused)
        if not self.is_initializing():
            # which attention the step ran, for whoever applies the encoder
            # with ``mutable=["stats"]`` (the joint step: onto ``loss.sync``)
            layers = jnp.int32(cfg.num_hidden_layers)
            sow_stats(self, "attn", {"layers": layers, "fused": layers * (fused is not None)})
        return x


# ---------------------------------------------------------------------------
# HF checkpoint conversion (rename/transpose only, like llm/convert.py)


def convert_hf_roberta(state_dict: dict, dtype=np.float32) -> dict:
    """torch/numpy HF RoBERTa/CodeBERT ``state_dict`` → Flax params tree for
    :class:`RobertaEncoder`. Accepts both bare ``RobertaModel`` names and the
    ``roberta.``-prefixed classifier checkpoints (LineVul publishes the
    latter); pooler/classifier/lm_head tensors are skipped (the fusion head
    owns classification)."""
    params: dict = {}

    def assign(path: list[str], value: np.ndarray) -> None:
        node = params
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = value

    for name, tensor in state_dict.items():
        arr = np.asarray(
            tensor.detach().cpu().float().numpy()
            if hasattr(tensor, "detach")
            else tensor,
            dtype=np.float32,
        )
        parts = name.split(".")
        if parts[0] == "roberta":
            parts = parts[1:]
        if parts[0] in ("pooler", "classifier", "lm_head", "qa_outputs"):
            continue
        if parts[0] == "embeddings":
            kind = parts[1]
            if kind == "LayerNorm":
                leaf = "scale" if parts[2] == "weight" else "bias"
                assign(["embeddings", "LayerNorm", leaf], arr.astype(dtype))
            elif kind.endswith("_embeddings"):
                assign(["embeddings", kind, "embedding"], arr.astype(dtype))
            continue
        if parts[0] == "encoder" and parts[1] == "layer":
            i, rest = parts[2], parts[3:]
            base = [f"layer_{i}"] + rest[:-2]
            mod, leaf = rest[-2], rest[-1]
            if mod == "LayerNorm":
                assign(base + ["LayerNorm", "scale" if leaf == "weight" else "bias"],
                       arr.astype(dtype))
            elif leaf == "weight":  # torch Linear [out, in] → Flax kernel [in, out]
                assign(base + [mod, "kernel"], arr.T.astype(dtype))
            elif leaf == "bias":
                assign(base + [mod, "bias"], arr.astype(dtype))
            continue
        # buffers (position_ids etc.): recomputed, skip
    return params
