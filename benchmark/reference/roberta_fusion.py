"""Plain reference: RoBERTa-family encoder fine-tuned end to end, with an
optional frozen GGNN embedding concatenated to the CLS vector before a 2-way
head (LineVul, and DeepDFA + LineVul). Serves every configuration whose file
says ``"reference": "roberta_fusion"``.

Written from the published descriptions — RoBERTa/CodeBERT (post-LN
transformer, learned positions counted from ``pad_token_id + 1`` over real
tokens, exact-erf GELU), DGL's ``GatedGraphConv`` + ``GlobalAttentionPooling``
as the DeepDFA paper configures them, HF ``get_cosine_schedule_with_warmup``
and AdamW with a global-norm clip — in straightforward ``jax.numpy`` and
numpy: no kernels, no padding of graphs, no batching tricks. It imports
nothing of ``deepdfa_tpu`` and takes nothing the program made: weights come
from :func:`make_weights` (the benchmark's own, from the seed), rows from the
traffic arrays by index.

Precision: float32 with ``Precision.HIGHEST`` matmuls. ``precision="fp8"`` is
the control — the same mathematics with every matmul operand of the forward
pass rounded to float8_e4m3 (per-tensor scale, straight-through gradient),
the nearest precision below the bfloat16 the configurations state.

Memory: each encoder layer is rematerialised, so a step at batch 16 x 512
holds one layer's activations at a time.
"""

from __future__ import annotations

import json
import math
from functools import partial

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
from jax import lax

COMPARISON = "train_steps"  # benchmark/comparisons/: what `run`'s readings are compared by
SUBKEYS = ("api", "datatype", "literal", "operator")
HI = lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# weights


def leaf_specs(cfg: dict) -> dict[str, tuple[tuple[int, ...], str, float]]:
    """name -> (shape, kind, std). Kinds: ``normal`` (0, std), ``ones``
    (1 + normal * std, a LayerNorm scale). Names are '/'-joined paths."""
    m = cfg["model"]
    h, ff = m["hidden_size"], m["intermediate_size"]
    out: dict[str, tuple[tuple[int, ...], str, float]] = {}

    def dense(name, fan_in, fan_out):
        out[f"{name}/kernel"] = ((fan_in, fan_out), "normal", 1.0 / math.sqrt(fan_in))
        out[f"{name}/bias"] = ((fan_out,), "normal", 0.02)

    def norm(name):
        out[f"{name}/scale"] = ((h,), "ones", 0.02)
        out[f"{name}/bias"] = ((h,), "normal", 0.02)

    e = "llm/embeddings"
    out[f"{e}/word_embeddings/embedding"] = ((m["vocab_size"], h), "normal", 0.02)
    out[f"{e}/position_embeddings/embedding"] = (
        (m["max_position_embeddings"], h), "normal", 0.02)
    out[f"{e}/token_type_embeddings/embedding"] = ((m["type_vocab_size"], h), "normal", 0.02)
    norm(f"{e}/LayerNorm")
    for i in range(m["num_hidden_layers"]):
        p = f"llm/layer_{i}"
        for proj in ("query", "key", "value"):
            dense(f"{p}/attention/self/{proj}", h, h)
        dense(f"{p}/attention/output/dense", h, h)
        norm(f"{p}/attention/output/LayerNorm")
        dense(f"{p}/intermediate/dense", h, ff)
        dense(f"{p}/output/dense", ff, h)
        norm(f"{p}/output/LayerNorm")
    head_in = h
    if cfg["use_gnn"]:
        g = cfg["gnn"]
        width = g["hidden_dim"] * len(SUBKEYS)
        f = "fusion/flowgnn_encoder"
        for sk in SUBKEYS:
            out[f"{f}/embed_{sk}/embedding"] = (
                (g["input_dim"], g["hidden_dim"]), "normal", 0.5)
        dense(f"{f}/ggnn/edge_linear", width, width)
        dense(f"{f}/ggnn/gru/x_proj", width, 3 * width)
        dense(f"{f}/ggnn/gru/h_proj", width, 3 * width)
        dense(f"{f}/pooling/gate", 2 * width, 1)
        head_in += 2 * width
    dense("fusion/classifier/dense", head_in, h)
    dense("fusion/classifier/out_proj", h, 2)
    return out


def is_frozen(cfg: dict, name: str) -> bool:
    return bool(cfg.get("freeze_gnn")) and "/flowgnn_encoder/" in name


def seed_key(seed: int) -> jax.Array:
    """A key from any whole seed up to 2**63 (two 31-bit halves folded)."""
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)


_JITTED: dict = {}  # one traced program per (what, configuration): seeds reuse it


def _memo(build, what: str, cfg: dict, *extra):
    """``build()`` once per (what, configuration, extra)."""
    key = (what, json.dumps(cfg, sort_keys=True), *extra)
    if key not in _JITTED:
        _JITTED[key] = build()
    return _JITTED[key]


def make_weights(cfg: dict, seed: int) -> dict[str, jax.Array]:
    """All float32 leaves on the device in one jitted call from the seed."""

    specs = leaf_specs(cfg)

    def make(key):
        out = {}
        for i, (name, (shape, kind, std)) in enumerate(specs.items()):
            z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * std
            out[name] = 1.0 + z if kind == "ones" else z
        return out

    return _memo(lambda: jax.jit(make), "weights", cfg)(seed_key(seed))


# --------------------------------------------------------------------------
# rounding for the control


@jax.custom_vjp
def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


_fp8.defvjp(lambda x: (_fp8(x), None), lambda _, g: (g,))


def _fp8_np(x: np.ndarray) -> np.ndarray:
    scale = max(float(np.max(np.abs(x))), 1e-30) / 448.0
    return (x / scale).astype(ml_dtypes.float8_e4m3fn).astype(np.float32) * scale


ROUND = {"f32": lambda x: x, "fp8": _fp8}
ROUND_NP = {"f32": lambda x: x, "fp8": _fp8_np}


# --------------------------------------------------------------------------
# the frozen GGNN, one graph at a time, in numpy


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def ggnn_embed(cfg: dict, w: dict[str, np.ndarray], graph: dict, precision: str) -> np.ndarray:
    """Pooled embedding ``[2 * width]`` of one graph: per-subkey embeddings
    concatenated, ``n_steps`` rounds of (Linear on the source state, sum over
    incoming edges, GRU cell with torch's r|z|n layout), then concat with the
    input embedding and gate-softmax attention pooling over the nodes."""
    rnd = ROUND_NP[precision]
    f = "fusion/flowgnn_encoder"
    x = np.concatenate(
        [w[f"{f}/embed_{sk}/embedding"][graph["node_feats"][f"_ABS_DATAFLOW_{sk}"]]
         for sk in SUBKEYS], axis=1).astype(np.float32)
    h = x
    width = h.shape[1]
    mm = lambda a, name: rnd(a) @ rnd(w[f"{f}/{name}/kernel"]) + w[f"{f}/{name}/bias"]
    for _ in range(cfg["gnn"]["n_steps"]):
        msg = mm(h, "ggnn/edge_linear")
        agg = np.zeros_like(h)
        np.add.at(agg, graph["receivers"], msg[graph["senders"]])
        xp, hp = mm(agg, "ggnn/gru/x_proj"), mm(h, "ggnn/gru/h_proj")
        r = _sigmoid(xp[:, :width] + hp[:, :width])
        z = _sigmoid(xp[:, width:2 * width] + hp[:, width:2 * width])
        n = np.tanh(xp[:, 2 * width:] + r * hp[:, 2 * width:])
        h = (1.0 - z) * n + z * h
    out = np.concatenate([h, x], axis=1)
    gate = mm(out, "pooling/gate")[:, 0]
    gate = np.exp(gate - gate.max())
    return ((gate / gate.sum())[:, None] * out).sum(0)


def graph_of(graphs: dict, i: int) -> dict:
    a, b = graphs["node_off"][i], graphs["node_off"][i + 1]
    e0, e1 = graphs["edge_off"][i], graphs["edge_off"][i + 1]
    return {
        "senders": graphs["senders"][e0:e1],
        "receivers": graphs["receivers"][e0:e1],
        "node_feats": {k: v[a:b] for k, v in graphs["node_feats"].items()},
    }


# --------------------------------------------------------------------------
# encoder, head, loss


def _layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * scale + bias


def _dense(rnd, w, name, x):
    return jnp.matmul(rnd(x), rnd(w[f"{name}/kernel"]), precision=HI) + w[f"{name}/bias"]


def _encoder_layer(cfg, rnd, lw, x, key_bias):
    """One post-LN block. ``lw`` holds this layer's leaves under short names."""
    m = cfg["model"]
    b, s, hid = x.shape
    heads = m["num_attention_heads"]
    d = hid // heads
    split = lambda t: t.reshape(b, s, heads, d)
    q = split(_dense(rnd, lw, "attention/self/query", x))
    k = split(_dense(rnd, lw, "attention/self/key", x))
    v = split(_dense(rnd, lw, "attention/self/value", x))
    scores = jnp.einsum("bqhd,bkhd->bhqk", rnd(q), rnd(k), precision=HI) / math.sqrt(d)
    probs = jax.nn.softmax(scores + key_bias, axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", rnd(probs), rnd(v), precision=HI).reshape(b, s, hid)
    eps = m["layer_norm_eps"]
    x = _layer_norm(_dense(rnd, lw, "attention/output/dense", ctx) + x,
                    lw["attention/output/LayerNorm/scale"],
                    lw["attention/output/LayerNorm/bias"], eps)
    ff = jax.nn.gelu(_dense(rnd, lw, "intermediate/dense", x), approximate=False)
    return _layer_norm(_dense(rnd, lw, "output/dense", ff) + x,
                       lw["output/LayerNorm/scale"], lw["output/LayerNorm/bias"], eps)


def loss_fn(cfg, precision, w, batch):
    """Mean cross-entropy over the rows with ``weight`` 1."""
    m = cfg["model"]
    rnd = ROUND[precision]
    ids, mask = batch["input_ids"], batch["pad_mask"]
    mi = mask.astype(jnp.int32)
    positions = jnp.cumsum(mi, axis=1) * mi + m["pad_token_id"]
    e = "llm/embeddings"
    x = (w[f"{e}/word_embeddings/embedding"][ids]
         + w[f"{e}/position_embeddings/embedding"][positions]
         + w[f"{e}/token_type_embeddings/embedding"][0])
    x = _layer_norm(x, w[f"{e}/LayerNorm/scale"], w[f"{e}/LayerNorm/bias"],
                    m["layer_norm_eps"])
    key_bias = jnp.where(mask[:, None, None, :], 0.0, -1e9)
    layer = jax.checkpoint(partial(_encoder_layer, cfg, rnd))
    for i in range(m["num_hidden_layers"]):
        p = f"llm/layer_{i}/"
        lw = {k[len(p):]: v for k, v in w.items() if k.startswith(p)}
        x = layer(lw, x, key_bias)
    first = jnp.argmax(mi, axis=1)  # the <s> of a left-padded row
    cls = jnp.take_along_axis(x, first[:, None, None], axis=1)[:, 0, :]
    if cfg["use_gnn"]:
        cls = jnp.concatenate([cls, batch["gnn_embed"]], axis=-1)
    hid = jnp.tanh(_dense(rnd, w, "fusion/classifier/dense", cls))
    logits = _dense(rnd, w, "fusion/classifier/out_proj", hid)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, batch["labels"][:, None], axis=1)[:, 0]
    wt = batch["weight"]
    return jnp.sum(ce * wt) / jnp.maximum(jnp.sum(wt), 1.0)


# --------------------------------------------------------------------------
# optimizer: clip by global norm, AdamW, warm-up then cosine


def learning_rate(cfg: dict, total_steps: int, count):
    t = cfg["train"]
    warm = max(total_steps // t["warmup_divisor"], 1)
    total = max(total_steps, warm + 1)
    count = jnp.asarray(count, jnp.float32)
    up = t["learning_rate"] * count / warm
    frac = jnp.clip((count - warm) / (total - warm), 0.0, 1.0)
    down = 0.5 * t["learning_rate"] * (1.0 + jnp.cos(jnp.pi * frac))
    return jnp.where(count < warm, up, down)


def _no_decay(name: str) -> bool:
    return name.endswith("/bias") or name.endswith("/scale")


def make_step(cfg: dict, total_steps: int, precision: str):
    """jitted ``(w, mu, nu, count, batch) -> (w, mu, nu, loss, grad_norms)``;
    ``grad_norms`` are per leaf, of the gradient after the clip — what Adam
    is handed. Frozen leaves get no gradient and no update."""
    t = cfg["train"]
    trained = [n for n in leaf_specs(cfg) if not is_frozen(cfg, n)]

    def step(w, mu, nu, count, batch):
        tw = {n: w[n] for n in trained}
        loss, g = jax.value_and_grad(
            lambda tw_: loss_fn(cfg, precision, {**w, **tw_}, batch))(tw)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
        clip = jnp.minimum(1.0, t["max_grad_norm"] / jnp.maximum(gnorm, 1e-30))
        g = {n: x * clip for n, x in g.items()}
        k = count + 1
        lr = learning_rate(cfg, total_steps, count)
        new_w, new_mu, new_nu = dict(w), {}, {}
        for n in trained:
            new_mu[n] = t["adam_b1"] * mu[n] + (1 - t["adam_b1"]) * g[n]
            new_nu[n] = t["adam_b2"] * nu[n] + (1 - t["adam_b2"]) * jnp.square(g[n])
            m_hat = new_mu[n] / (1 - t["adam_b1"] ** k)
            v_hat = new_nu[n] / (1 - t["adam_b2"] ** k)
            upd = m_hat / (jnp.sqrt(v_hat) + t["adam_epsilon"])
            if t["weight_decay"] and not _no_decay(n):
                upd = upd + t["weight_decay"] * w[n]
            new_w[n] = w[n] - lr * upd
        norms = {n: jnp.sqrt(jnp.sum(jnp.square(x))) for n, x in g.items()}
        return new_w, new_mu, new_nu, loss, norms

    return _memo(lambda: jax.jit(step), "step", cfg, total_steps, precision), trained


# --------------------------------------------------------------------------
# the readings the comparison uses


def build_batch(cfg: dict, data: dict, w_host: dict | None, rows, precision: str,
                fault: str | None = None) -> dict:
    """Rows ``rows`` of the traffic arrays as the reference wants them: the
    token block and its mask as generated, every row weighted 1, and each
    row's own graph embedded on the host."""
    rows = np.asarray(rows, np.int64)
    weight = np.ones(rows.size, np.float32)
    if fault == "half_batch":
        weight[rows.size // 2:] = 0.0
    batch = {
        "input_ids": data["input_ids"][rows],
        "pad_mask": data["pad_mask"][rows],
        "labels": data["labels"][rows].astype(np.int32),
        "weight": weight,
    }
    if cfg["use_gnn"]:
        batch["gnn_embed"] = np.stack([
            ggnn_embed(cfg, w_host, graph_of(data["graphs"], int(i)), precision)
            for i in rows]).astype(np.float32)
    return batch


def run(cfg: dict, data: dict, seed: int, step_rows: list, total_steps: int,
        precision: str = "f32", fault: str | None = None) -> dict:
    """Follow ``len(step_rows)`` steps from the seed's weights over the given
    rows. Returns ``loss`` (one a step), ``grad1`` (per-leaf norm of the first
    gradient as Adam gets it) and ``delta`` (per-leaf norm of the parameters'
    change after the last step); frozen leaves have ``delta`` 0 and no
    ``grad1``."""
    w0 = make_weights(cfg, seed)
    step, trained = make_step(cfg, total_steps, precision)
    w_host = None
    if cfg["use_gnn"]:
        w_host = {n: np.asarray(v) for n, v in w0.items() if "/flowgnn_encoder/" in n}
    w = w0
    mu = {n: jnp.zeros_like(w0[n]) for n in trained}
    nu = {n: jnp.zeros_like(w0[n]) for n in trained}
    losses, grad1 = [], None
    for count, rows in enumerate(step_rows):
        batch = build_batch(cfg, data, w_host, rows, precision, fault)
        if fault == "state_unchanged":
            _, _, _, loss, norms = step(w, mu, nu, count, batch)
        else:
            w, mu, nu, loss, norms = step(w, mu, nu, count, batch)
        losses.append(float(loss))
        if count == 0:
            grad1 = {n: float(v) for n, v in jax.device_get(norms).items()}
    diff = _memo(lambda: jax.jit(lambda a, b: {
        n: jnp.sqrt(jnp.sum(jnp.square(a[n] - b[n]))) for n in a}), "delta", cfg)
    delta = jax.device_get(diff(w, w0))
    return {"loss": losses, "grad1": grad1,
            "delta": {n: float(v) for n, v in delta.items()}}
