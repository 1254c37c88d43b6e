"""Plain reference: MSIVD's joint classifier over a frozen decoder whose
attention is degree-2 power retention (the Brumby layer) — the decoder's
final-norm hidden states pooled at the last real token, joined with the
*trained* GGNN's graph embedding, a 2-way head. Serves every configuration
whose file says ``"reference": "brumby_fusion"``.

The layer, as computed here (input ``x`` [tokens, hidden]; RMSNorm ``N``, eps
from the configuration; no biases but the gate's)::

    n = N_in(x)
    q = N_q(n W_q) [heads x d] ; k = N_k(n W_k) [kv heads x d] ; v = n W_v      N_q, N_k over d, per head
    q, k rotated: (x1, x2) = the two halves of d, (x1 cos - x2 sin, x2 cos + x1 sin),
            angle = p * theta^(-2j/d), p counting the row's REAL tokens from 0
    log g_t = logsigmoid(n_t W_g + b_g)      [kv heads]
    w_tj = (q_t . k_j / sqrt d)^2 * exp(G_t - G_j) for real j <= t, else 0    G: log g summed over real tokens
    o_t = sum_j w_tj v_j / (sum_j w_tj + eps)         query head h reads key/value head h // (heads / kv heads)
    a = x + o W_o
    y = a + (silu(m W_gate) * (m W_up)) W_down ,  m = N_post(a)

**The attention form, not the recurrence.** The program computes a recurrent
state (``phi(k) v^T`` summed, chunk by chunk, in a kernel); this file computes
the weights ``w_tj`` of every query-key pair whole and normalises them, in
blocks of queries, one row and one key/value head's query heads at a time. The
two share no algorithm: no feature map, no chunk, no state.

Written in straightforward ``jax.numpy``, float32, ``Precision.HIGHEST``. The
trained part — GGNN over each row's own graph, head, loss, clip, AdamW — *is*
``reference/longcat_fusion.py``'s (``roberta_fusion``'s GGNN), imported, as
is the lazy per-leaf mapping of the weights. It imports nothing of
``deepdfa_tpu``. One layer's weights are on the chip at a time (10 layers are
13.2 GB in float32). Leaves are named per layer (``llm/layers_<i>/...``); the
program stacks them on a leading layer axis (its layers are one scan).

Weights from ``(seed, leaf name)``; ``b_g`` is not drawn as the matrices are
but set (the configuration file's ``assumed``): half-lives log-uniform over
``gate_half_lives`` across the key/value heads, ``sigmoid(b) =
2^(-1/half-life)`` — random biases would give gates that forget within a token.

``precision="fp8"`` is the control (every matmul operand of the forward pass
rounded to float8_e4m3); ``fault=`` plants one fault (``FAULTS``).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from harness import spec
from jax import lax

_LC = spec.load_module("reference", "longcat_fusion")  # everything but the decoder
_BASE = _LC._BASE
COMPARISON = "frozen_dense_train_steps"
SUBKEYS = _LC.SUBKEYS
HI = lax.Precision.HIGHEST
ROUND = _LC.ROUND
seed_key = _LC.seed_key
is_trained = _LC.is_trained
_mm, _rms, _ffn = _LC._mm, _LC._rms, _LC._ffn

FAULTS = (
    "half_batch", "state_unchanged", "degree_1", "gate_dropped", "normaliser_dropped",
    "pads_in_state", "state_not_carried", "kv_head_mod", "qk_norm_skipped", "rope_dropped",
)
FLOAT32_LEAVES = ("g_bias",)  # what a bfloat16 program keeps in float32 too
BLOCK_Q = 512  # queries a block of the attention form


# --------------------------------------------------------------------------
# weights


def model_of(cfg: dict) -> dict:
    """The decoder's sizes: the published keys at the top level of the
    configuration file, and the assumed retention constants."""
    m = {k: cfg[k] for k in (
        "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta")}
    if cfg["hidden_act"] != "silu" or cfg["attention_bias"] or cfg["rope_scaling"] is not None:
        raise ValueError("this reference writes a silu MLP, unbiased projections and plain RoPE")
    r = cfg["retention"]
    m["eps"], m["half_lives"], m["chunk"] = r["eps"], tuple(r["gate_half_lives"]), r["chunk"]
    return m


def leaf_specs(cfg: dict) -> dict[str, tuple[tuple[int, ...], str, float]]:
    """name -> (shape, kind, std), kinds as ``longcat_fusion.leaf_specs`` has
    them plus ``g_bias`` (module docstring). Names are the program's tree paths
    with the layer index in the name. Every matrix is made at 1/sqrt(fan_in):
    the projections of a normed input have unit-variance entries, so scores
    over sqrt(d) have unit spread as made."""
    m = model_of(cfg)
    h, ff, d = m["hidden_size"], m["intermediate_size"], m["head_dim"]
    heads, hk = m["num_attention_heads"], m["num_key_value_heads"]
    out: dict[str, tuple[tuple[int, ...], str, float]] = {}

    def kernel(name, fan_in, fan_out):
        out[f"{name}/kernel"] = ((fan_in, fan_out), "normal16", 1.0 / math.sqrt(fan_in))

    def norm(name, width):
        out[f"{name}/weight"] = ((width,), "ones16", 0.02)

    out["llm/embed_tokens/embedding"] = ((m["vocab_size"], h), "normal16", 1.0)
    for i in range(m["num_hidden_layers"]):
        p = f"llm/layers_{i}"
        norm(f"{p}/input_norm", h)
        r = f"{p}/retention"
        kernel(f"{r}/q_proj", h, heads * d)
        kernel(f"{r}/k_proj", h, hk * d)
        kernel(f"{r}/v_proj", h, hk * d)
        kernel(f"{r}/g_proj", h, hk)
        out[f"{r}/g_bias"] = ((hk,), "g_bias", 0.0)
        norm(f"{r}/q_norm", d)
        norm(f"{r}/k_norm", d)
        kernel(f"{r}/o_proj", heads * d, h)
        norm(f"{p}/post_attn_norm", h)
        kernel(f"{p}/mlp/gate_proj", h, ff)
        kernel(f"{p}/mlp/up_proj", h, ff)
        kernel(f"{p}/mlp/down_proj", ff, h)
    norm("llm/norm", h)

    def dense(name, fan_in, fan_out):
        out[f"{name}/kernel"] = ((fan_in, fan_out), "normal", 1.0 / math.sqrt(fan_in))
        out[f"{name}/bias"] = ((fan_out,), "normal", 0.02)

    head_in = h
    if cfg["use_gnn"]:
        g = cfg["gnn"]
        w = g["hidden_dim"] * len(SUBKEYS)
        fg = "fusion/flowgnn_encoder"
        for sk in SUBKEYS:
            out[f"{fg}/embed_{sk}/embedding"] = ((g["input_dim"], g["hidden_dim"]), "normal", 0.5)
        dense(f"{fg}/ggnn/edge_linear", w, w)
        dense(f"{fg}/ggnn/gru/x_proj", w, 3 * w)
        dense(f"{fg}/ggnn/gru/h_proj", w, 3 * w)
        dense(f"{fg}/pooling/gate", 2 * w, 1)
        head_in += 2 * w
    dense("fusion/classifier/dense", head_in, h)
    dense("fusion/classifier/out_proj", h, 2)
    return out


def gate_bias(half_lives: tuple, n: int) -> jax.Array:
    """``b_g`` [n]: half-lives evenly spaced in log over ``half_lives``,
    ``sigmoid(b) = 2^(-1/half-life)``."""
    life = np.exp(np.linspace(math.log(half_lives[0]), math.log(half_lives[1]), n))
    g = np.exp2(-1.0 / life)
    return jnp.asarray(np.log(g) - np.log1p(-g), jnp.float32)


class Weights(_LC.Weights):
    """``longcat_fusion.Weights`` over this decoder's leaves, with the gate's
    bias set and not drawn."""

    def __init__(self, cfg: dict, seed: int):
        self.specs, self.key = leaf_specs(cfg), seed_key(seed)
        self.half_lives = model_of(cfg)["half_lives"]

    def __getitem__(self, name: str) -> jax.Array:
        shape, kind, _ = self.specs[name]
        if kind == "g_bias":
            return gate_bias(self.half_lives, shape[0])
        return super().__getitem__(name)


def make_weights(cfg: dict, seed: int) -> Weights:
    return Weights(cfg, seed)


# --------------------------------------------------------------------------
# the decoder


def _rope(x, pos, theta):
    """Rotate-half over all of the last axis: x [s, heads, d], pos [s]."""
    d = x.shape[-1]
    ang = pos[:, None] * (1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _retention(m, rnd, fault, w, x, mask):
    """One row: x [s, hidden] (normed), mask [s] -> [s, hidden]."""
    s = x.shape[0]
    heads, hk, d = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    rep = heads // hk
    q = _mm(rnd, x, w["q_proj/kernel"]).reshape(s, heads, d)
    k = _mm(rnd, x, w["k_proj/kernel"]).reshape(s, hk, d)
    v = _mm(rnd, x, w["v_proj/kernel"]).reshape(s, hk, d)
    if fault != "qk_norm_skipped":
        q = _rms(q, w["q_norm/weight"], m["rms_norm_eps"])
        k = _rms(k, w["k_norm/weight"], m["rms_norm_eps"])
    if fault != "rope_dropped":
        real = jnp.maximum(jnp.cumsum(mask) - 1, 0).astype(jnp.float32)  # the first real token is 0
        q, k = _rope(q, real, m["rope_theta"]), _rope(k, real, m["rope_theta"])
    log_g = jax.nn.log_sigmoid(_mm(rnd, x, w["g_proj/kernel"]) + w["g_bias"])  # [s, hk]
    if fault == "gate_dropped":
        log_g = jnp.zeros_like(log_g)
    g = jnp.cumsum(jnp.where(mask[:, None], log_g, 0.0), axis=0)  # [s, hk]
    if fault == "kv_head_mod":  # query head h reads key/value head h % hk
        order = np.argsort(np.arange(heads) % hk, kind="stable")  # heads grouped by h % hk
        q = q[:, order]
    pos = jnp.arange(s)
    key_ok = jnp.ones_like(mask) if fault == "pads_in_state" else mask
    blocks = s // min(BLOCK_Q, s)
    bq = s // blocks

    def group(qkvg):  # one key/value head and the query heads that read it
        qg, kg, vg, gg = qkvg  # [rep, s, d], [s, d], [s, d], [s]

        def block(i):  # queries [i bq, (i + 1) bq)
            t = i * bq + jnp.arange(bq)
            qb = lax.dynamic_slice_in_dim(qg, i * bq, bq, axis=1)
            ok = (pos[None, :] <= t[:, None]) & key_ok[None, :]
            if fault == "state_not_carried":
                ok &= pos[None, :] // m["chunk"] == t[:, None] // m["chunk"]
            gt = lax.dynamic_slice_in_dim(gg, i * bq, bq)
            decay = jnp.exp(jnp.where(ok, gt[:, None] - gg[None, :], -jnp.inf))  # [bq, s]
            scores = jnp.einsum("hqd,kd->hqk", rnd(qb), rnd(kg), precision=HI) / math.sqrt(d)
            wts = (scores if fault == "degree_1" else jnp.square(scores)) * decay[None]
            num = jnp.einsum("hqk,kd->hqd", rnd(wts), rnd(vg), precision=HI)
            if fault == "normaliser_dropped":
                return num
            return num / (jnp.sum(wts, -1, keepdims=True) + m["eps"])

        out = lax.map(block, jnp.arange(blocks))  # [blocks, rep, bq, d]
        return out.transpose(1, 0, 2, 3).reshape(qg.shape)

    ctx = lax.map(group, (q.reshape(s, hk, rep, d).transpose(1, 2, 0, 3),
                          k.transpose(1, 0, 2), v.transpose(1, 0, 2), g.T))  # [hk, rep, s, d]
    ctx = ctx.transpose(2, 0, 1, 3).reshape(s, heads, d)
    if fault == "kv_head_mod":
        ctx = ctx[:, np.argsort(order)]
    return _mm(rnd, ctx.reshape(s, heads * d), w["o_proj/kernel"])


def _layer(m, precision, fault, lw, h, mask):
    """One layer over the batch: h [b, s, hidden], mask [b, s]."""
    rnd = ROUND[precision]
    eps = m["rms_norm_eps"]
    sub = lambda prefix: {k[len(prefix) + 1:]: v for k, v in lw.items()
                          if k.startswith(prefix + "/")}
    x = _rms(h, lw["input_norm/weight"], eps)
    a = h + lax.map(lambda xm: _retention(m, rnd, fault, sub("retention"), *xm), (x, mask))
    f = sub("mlp")
    u = _rms(a, lw["post_attn_norm/weight"], eps)
    return a + _ffn(rnd, u, f["gate_proj/kernel"], f["up_proj/kernel"], f["down_proj/kernel"])


def decoder(cfg: dict, w: Weights, ids, mask, precision="f32", fault=None):
    """Final-norm hidden states [b, s, hidden]."""
    m = model_of(cfg)
    h = w["llm/embed_tokens/embedding"][ids]
    layer = _BASE._memo(lambda: jax.jit(partial(_layer, m, precision, fault)),
                        "brumby_layer", cfg, precision, fault)
    for i in range(m["num_hidden_layers"]):
        lw = w.under(f"llm/layers_{i}")  # this layer's alone
        h = layer(lw, h, mask)
        del lw
    return _rms(h, w["llm/norm/weight"], m["rms_norm_eps"])


# --------------------------------------------------------------------------
# the readings the comparison uses


def run(cfg: dict, data: dict, seed: int, step_rows: list, total_steps: int,
        routing: None = None, precision: str = "f32", fault: str | None = None) -> dict:
    """Follow ``len(step_rows)`` steps from the seed's weights over the given
    rows, the trained part through ``longcat_fusion.make_step``. Returns
    ``loss``, ``grad1``, ``delta`` (per trained leaf) and, per step, ``hidden``
    (final-norm states of the real tokens, [n_real, hidden]), ``logits`` and
    ``real`` (the pad mask). ``routing`` is the routed references' argument:
    this decoder routes nothing, takes none and hands none back."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    w = make_weights(cfg, seed)
    step = _LC.make_step(cfg, total_steps, precision)
    tw0 = {n: w[n] for n in w if is_trained(n)}
    tw = tw0
    mu = {n: jnp.zeros_like(v) for n, v in tw0.items()}
    nu = {n: jnp.zeros_like(v) for n, v in tw0.items()}
    out: dict = {"routing": None, **{k: [] for k in ("loss", "hidden", "logits", "real")}}
    for count, rows in enumerate(step_rows):
        rows = np.asarray(rows, np.int64)
        mask = data["pad_mask"][rows]
        h = decoder(cfg, w, data["input_ids"][rows], mask, precision, fault)
        last = mask.shape[1] - 1 - np.argmax(mask[:, ::-1], axis=1)  # last real token
        pooled = h[np.arange(len(rows)), last]
        weight = np.ones(len(rows), np.float32)
        if fault == "half_batch":
            weight[len(rows) // 2:] = 0.0
        graphs = _LC.pad_graphs(data, rows) if cfg["use_gnn"] else None
        new = step(tw, mu, nu, count, pooled, graphs,
                   data["labels"][rows].astype(np.int32), weight)
        if fault != "state_unchanged":
            tw, mu, nu = new[:3]
        out["loss"].append(float(new[3]))
        out["logits"].append(np.asarray(new[4]))
        if count == 0:
            out["grad1"] = {n: float(v) for n, v in jax.device_get(new[5]).items()}
        out["hidden"].append(np.asarray(h)[mask])
        out["real"].append(mask)
    out["delta"] = {n: float(jnp.sqrt(jnp.sum(jnp.square(tw[n] - tw0[n])))) for n in tw0}
    return out
