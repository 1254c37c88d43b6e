"""Plain reference: MSIVD's joint classifier over a frozen decoder of
compressed convolutional attention and top-1 MLP-routed experts (the ZAYA1
layer) — the decoder's final-norm hidden states pooled at the last real token,
joined with the *trained* GGNN's graph embedding, a 2-way head. Serves every
configuration whose file says ``"reference": "zaya_fusion"``.

The layer, as computed here (input ``x`` [tokens, hidden]; RMSNorm ``N``, eps
from the configuration; no biases; ``x_{t-1}`` is the previous position's
value with the pads zeroed first, so 0 at a row's first real token)::

    n  = N_in(x)
    q~, k~ = n W_q [heads x d], n W_k [kv heads x d] ; u = [q~ ; k~]
    u1_t = w1[0] u_t + w1[1] u_{t-1}                    depthwise, cca_time0 = 2
    u2_t = u1_t W2[0, head] + u1_{t-1} W2[1, head]      grouped by head, cca_time1 = 2
    q_h = u2_q,h + (q~_h + k~_g(h)) / 2 ; k_g = u2_k,g + (k~_g + mean over g(h) = g of q~_h) / 2
    q^_h = q_h / |q_h| sqrt(d) ; k^_g = k_g / |k_g| tau_g
    dims [0, rot) of q^, k^ turned: (x1, x2) the halves of those rot dims,
        (x1 cos - x2 sin, x2 cos + x1 sin), angle = p theta^(-2j/rot), p counting the row's REAL tokens from 0
    v = [n_t W_v[:, :d] ; n_{t-1} W_v[:, d:]]           value head 1 reads the previous token
    a = x + softmax(q^ k^T / sqrt(d) + causal, pad mask) v W_o     query head h reads kv head h // (heads / kv heads)
    m = N_post(a)
    r_l = m W_down ; r~_l = (1 - sigmoid(e_l)) r_l + sigmoid(e_l) r~_{l-1}   (r~_0 = r_0)
    l = gelu(gelu(r~_l W_1) W_2) W_3          [experts + 1], gelu exact (erf)
    c = argmax(l + b) ; g = softmax(l)[c]
    y = a + g (silu(m W_gate_c) * (m W_up_c)) W_down_c   for a held expert c
    y = a + g m                                          for c = experts (the skip)

What absent experts would add is left out, as in the program. Departures from
the published description, and the points the published config does not
settle, are the configuration file's ``assumed``.

Written in straightforward ``jax.numpy``, float32, ``Precision.HIGHEST``: no
kernel, no grouped product; the convolutions as shifted copies; experts as a
loop over the held ones with masks over all tokens; attention one row at a
time, in blocks of ``BLOCK_Q`` queries each against all of the row's keys
(8 heads' scores of one 8,192-token row are 2.1 GB). The router's products
are never rounded. The trained part — GGNN over each row's own graph, head,
loss, clip, AdamW — *is* ``reference/longcat_fusion.py``'s, imported, as are
the lazy per-leaf weights. It imports nothing of ``deepdfa_tpu``. One layer's
weights are on the chip at a time.

**Routing under rounding** is ``smallthinker_fusion.py``'s rule at top-1 over
the experts and the skip, with ``l + b`` in the place of its logits: ``run``
takes the program's choice (``routing``) at a token-layer only where the two
options the sides disagree on lie, by the reference's own ``l + b``, within a
band narrower than ``check.route_epsilon``; the gate then follows the choice
taken. The EDA state is the reference's own whatever the choice.

``precision="fp8"`` is the control (every matmul operand of the forward pass
rounded to float8_e4m3; the router's products never are); ``fault=`` plants
one fault (``FAULTS``).
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
COMPARISON = "frozen_train_steps"
SUBKEYS = _LC.SUBKEYS
HI = lax.Precision.HIGHEST
ROUND = _LC.ROUND
seed_key = _LC.seed_key
is_trained = _LC.is_trained
_mm, _rms = _LC._mm, _LC._rms

FAULTS = (
    "half_batch", "state_unchanged", "value_shift_dropped", "qk_mean_dropped",
    "depthwise_conv_dropped", "grouped_conv_dropped", "temperature_dropped", "rope_whole_head",
    "eda_dropped", "skip_never_taken", "expert_skipped",
)
BLOCK_Q = 2048  # queries a block of the attention
TAU_SCALE = 8.0  # a temperature leaf is 8 (1 + 0.1 z): scores tau cos(q, k) spread about 0.7
# what a bfloat16 program keeps in float32 too: the router's leaves
FLOAT32_LEAVES = tuple(f"/router/{n}" for n in ("down", "mlp_1", "mlp_2", "mlp_3", "bias", "eda"))


# --------------------------------------------------------------------------
# weights


def model_of(cfg: dict) -> dict:
    """The decoder's sizes: the published keys at the top level of the
    configuration file, the experts held beside the router's width."""
    m = {k: cfg[k] for k in (
        "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "router_hidden_size", "num_experts",
        "moe_intermediate_size", "rms_norm_eps", "partial_rotary_factor")}
    assert (cfg["cca_time0"], cfg["cca_time1"], cfg["num_experts_per_tok"]) == (2, 2, 1)
    assert cfg["num_key_value_heads"] == 2 and cfg["hidden_act"] == "silu"
    hybrid = cfg["rope_parameters"]["hybrid"]
    assert hybrid["partial_rotary_factor"] == cfg["partial_rotary_factor"]
    m["rope_theta"] = hybrid["rope_theta"]
    m["rot"] = int(cfg["head_dim"] * cfg["partial_rotary_factor"])
    m["lo"], hi = cfg["experts_held"]
    m["n_held"] = hi - m["lo"]
    return m


def leaf_specs(cfg: dict) -> dict[str, tuple[tuple[int, ...], str, float]]:
    """name -> (shape, kind, std), kinds as ``longcat_fusion.leaf_specs`` has
    them. Names are the program's tree paths. Projections at 1/sqrt(fan_in);
    the convolutions so that each half of ``q`` and ``k`` (convolved and
    qk-mean) carries about half their variance; the router so that its
    logits spread by about 0.8 over its options (``TAU_SCALE`` for the
    attention's scores)."""
    m = model_of(cfg)
    h, f, d = m["hidden_size"], m["moe_intermediate_size"], m["head_dim"]
    heads, kv, r = m["num_attention_heads"], m["num_key_value_heads"], m["router_hidden_size"]
    out: dict[str, tuple[tuple[int, ...], str, float]] = {}

    def kernel(name, fan_in, fan_out):
        out[f"{name}/kernel"] = ((fan_in, fan_out), "normal16", 1.0 / math.sqrt(fan_in))

    def norm(name, n):
        out[f"{name}/weight"] = ((n,), "ones16", 0.02)

    out["llm/embed_tokens/embedding"] = ((m["vocab_size"], h), "normal16", 1.0)
    for i in range(m["num_hidden_layers"]):
        p = f"llm/layers_{i}"
        norm(f"{p}/input_norm", h)
        norm(f"{p}/post_attn_norm", h)
        kernel(f"{p}/attn/q_proj", h, heads * d)
        kernel(f"{p}/attn/k_proj", h, kv * d)
        kernel(f"{p}/attn/v_proj", h, kv * d)
        kernel(f"{p}/attn/o_proj", heads * d, h)
        out[f"{p}/attn/conv_depthwise"] = ((2, (heads + kv) * d), "normal16", 0.5)
        out[f"{p}/attn/conv_grouped"] = ((2, heads + kv, d, d), "normal16", 1.0 / math.sqrt(2 * d))
        out[f"{p}/attn/temperature"] = ((kv,), "ones16", 0.1)  # times TAU_SCALE: Weights
        out[f"{p}/router/down"] = ((h, r), "normal16", 1.0 / math.sqrt(h))
        if i:
            out[f"{p}/router/eda"] = ((), "normal16", 1.0)
        out[f"{p}/router/mlp_1"] = ((r, r), "normal16", 1.0 / math.sqrt(r))
        out[f"{p}/router/mlp_2"] = ((r, r), "normal16", 1.5 / math.sqrt(r))
        out[f"{p}/router/mlp_3"] = ((r, m["num_experts"] + 1), "normal16", 1.5 / math.sqrt(r))
        out[f"{p}/router/bias"] = ((m["num_experts"] + 1,), "normal16", 0.1)
        out[f"{p}/moe/experts_gate"] = ((m["n_held"], h, f), "normal16", 1.0 / math.sqrt(h))
        out[f"{p}/moe/experts_up"] = ((m["n_held"], h, f), "normal16", 1.0 / math.sqrt(h))
        out[f"{p}/moe/experts_down"] = ((m["n_held"], f, h), "normal16", 1.0 / math.sqrt(f))
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


class Weights(_LC.Weights):
    """``longcat_fusion.Weights`` over this decoder's leaves; a temperature
    leaf times ``TAU_SCALE`` (a power of two: still bfloat16-representable)."""

    def __init__(self, cfg: dict, seed: int):
        self.specs, self.key = leaf_specs(cfg), seed_key(seed)

    def __getitem__(self, name: str) -> jax.Array:
        leaf = super().__getitem__(name)
        return leaf * TAU_SCALE if name.endswith("/temperature") else leaf


def make_weights(cfg: dict, seed: int) -> Weights:
    return Weights(cfg, seed)


# --------------------------------------------------------------------------
# the decoder


def _prev(x, mask):
    """``x_{t-1}`` of one row x [s, ...], the pads zeroed first."""
    x = jnp.where(mask.reshape(mask.shape + (1,) * (x.ndim - 1)), x, 0.0)
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], 0)


def _rope(x, pos, theta, rot):
    """Rotate-half over the first ``rot`` dims: x [s, heads, d], pos [s]."""
    ang = pos[:, None] * (1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], -1)


def _attention(m, rnd, fault, w, x, mask):
    """One row: x [s, hidden], mask [s] -> [s, hidden]."""
    s = x.shape[0]
    heads, kv, d = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    rep = heads // kv
    real = mask[:, None]
    q0 = jnp.where(real, _mm(rnd, x, w["q_proj/kernel"]), 0.0)
    k0 = jnp.where(real, _mm(rnd, x, w["k_proj/kernel"]), 0.0)
    v = jnp.where(real, _mm(rnd, x, w["v_proj/kernel"]), 0.0)
    u = jnp.concatenate([q0, k0], -1)
    w1 = w["conv_depthwise"]
    u1 = u if fault == "depthwise_conv_dropped" else w1[0] * u + w1[1] * _prev(u, mask)
    u1 = jnp.where(real, u1, 0.0).reshape(s, heads + kv, d)
    w2 = w["conv_grouped"]
    tap = lambda a, wk: jnp.einsum("sgd,gde->sge", rnd(a), rnd(wk), precision=HI)
    u2 = u1 if fault == "grouped_conv_dropped" else tap(u1, w2[0]) + tap(_prev(u1, mask), w2[1])
    qh0, kh0 = q0.reshape(s, heads, d), k0.reshape(s, kv, d)
    q, k = u2[:, :heads], u2[:, heads:]
    if fault != "qk_mean_dropped":
        q = q + (qh0 + jnp.repeat(kh0, rep, axis=1)) / 2
        k = k + (kh0 + qh0.reshape(s, kv, rep, d).mean(2)) / 2
    unit = lambda a: a / jnp.maximum(jnp.linalg.norm(a, axis=-1, keepdims=True), 1e-12)
    tau = jnp.ones(kv) if fault == "temperature_dropped" else w["temperature"]
    q, k = unit(q) * math.sqrt(d), unit(k) * tau[:, None]
    pos = jnp.maximum(jnp.cumsum(mask) - 1, 0).astype(jnp.float32)  # the first real token is 0
    rot = d if fault == "rope_whole_head" else m["rot"]
    q, k = _rope(q, pos, m["rope_theta"], rot), _rope(k, pos, m["rope_theta"], rot)
    v_prev = v[:, d:] if fault == "value_shift_dropped" else _prev(v[:, d:], mask)
    v = jnp.stack([v[:, :d], v_prev], 1)  # [s, kv, d]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    bq = math.gcd(s, BLOCK_Q)
    t = jnp.arange(s)

    def block(i):  # queries [i * bq, (i + 1) * bq) against every key of the row
        qb = lax.dynamic_slice_in_dim(q, i * bq, bq)
        tq = i * bq + jnp.arange(bq)
        ok = (t[None, :] <= tq[:, None]) & mask[None, :]
        scores = jnp.einsum("qhd,khd->hqk", rnd(qb), rnd(k), precision=HI) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(ok[None], scores, -1e30), axis=-1)
        probs = jnp.where(jnp.any(ok, -1)[None, :, None], probs, 0.0)  # a pad attends to nothing
        return jnp.einsum("hqk,khd->qhd", rnd(probs), rnd(v), precision=HI)

    ctx = lax.map(block, jnp.arange(s // bq)).reshape(s, heads * d)
    return _mm(rnd, ctx, w["o_proj/kernel"])


def _moe(m, fault, eps_route, w, u, real, prev, prog_choice, rnd):
    """u [t, hidden] (the router's and the experts' input), real [t], prev
    the layer before's EDA state or None -> (out, r~, used, own, band)."""
    lo, n_held, skip = m["lo"], m["n_held"], m["num_experts"]
    dot = lambda a, b: jnp.matmul(a, b, precision=HI)  # the router: never rounded
    r = dot(u, w["down"])
    if prev is not None and fault != "eda_dropped":
        gamma = jax.nn.sigmoid(w["eda"])
        r = (1.0 - gamma) * r + gamma * prev
    gelu = partial(jax.nn.gelu, approximate=False)
    logits = dot(gelu(dot(gelu(dot(r, w["mlp_1"])), w["mlp_2"])), w["mlp_3"])
    score = logits + w["bias"]
    if fault == "skip_never_taken":
        score = score.at[:, skip].set(-jnp.inf)
    own = jnp.argmax(score, -1)[:, None]
    if prog_choice is None:
        used, band = own, jnp.zeros(u.shape[0], jnp.float32)
    else:
        # the two options the sides disagree on, by the reference's own score
        theirs = jnp.take_along_axis(score, jnp.maximum(prog_choice, 0), axis=-1)[:, 0]
        mine = jnp.take_along_axis(score, own, axis=-1)[:, 0]
        differ = (own[:, 0] != prog_choice[:, 0]) & real
        band = jnp.where(differ, mine - theirs, 0.0)
        used = jnp.where((band < eps_route)[:, None], prog_choice, own)
    g = jnp.take_along_axis(jax.nn.softmax(logits, -1), used, axis=-1) * real[:, None]
    out = jnp.where(used == skip, g, 0.0) * u
    held = (used >= lo) & (used < lo + n_held)

    def expert(acc, ew):  # every token through every held expert, masked by its gate
        e, gate, up, down = ew
        ge = jnp.where(held & (used == lo + e), g, 0.0)
        if fault == "expert_skipped":
            ge = ge * (e != n_held // 2)
        return acc + ge * _mm(rnd, jax.nn.silu(_mm(rnd, u, gate)) * _mm(rnd, u, up), down), None

    out, _ = lax.scan(expert, out, (
        jnp.arange(n_held), w["experts_gate"], w["experts_up"], w["experts_down"]))
    return out, r, used, own, band


def _layer(m, precision, fault, eps_route, lw, h, mask, prev, prog_choice):
    """One layer over the batch: h [b, s, hidden], mask [b, s], prev the EDA
    state [b * s, router width] or None."""
    rnd = ROUND[precision]
    b, s, hid = h.shape
    eps = m["rms_norm_eps"]
    sub = lambda prefix: {k[len(prefix) + 1:]: v for k, v in lw.items()
                          if k.startswith(prefix + "/")}
    n = _rms(h, lw["input_norm/weight"], eps)
    a = h + lax.map(lambda xm: _attention(m, rnd, fault, sub("attn"), *xm), (n, mask))
    u = _rms(a, lw["post_attn_norm/weight"], eps)
    out, r, used, own, band = _moe(
        m, fault, eps_route, {**sub("router"), **sub("moe")}, u.reshape(b * s, hid),
        mask.reshape(b * s), prev, None if prog_choice is None else prog_choice.reshape(b * s, -1),
        rnd)
    return (a + out.reshape(b, s, hid), r, used.reshape(b, s, 1), own.reshape(b, s, 1),
            band.reshape(b, s))


def decoder(cfg: dict, w: Weights, ids, mask, routing=None, precision="f32", fault=None):
    """Final-norm hidden states [b, s, hidden] and, per layer, the choices
    used, the reference's own, and the bands ([layers, b, s, ...])."""
    m = model_of(cfg)
    eps_route = cfg["check"]["route_epsilon"]
    h = w["llm/embed_tokens/embedding"][ids]
    prev, used, own, band = None, [], [], []
    for i in range(m["num_hidden_layers"]):
        layer = _BASE._memo(
            lambda: jax.jit(partial(_layer, m, precision, fault, eps_route)),
            "zaya_layer", cfg, precision, fault, i == 0, routing is None)
        lw = w.under(f"llm/layers_{i}")  # this layer's alone
        h, prev, u_, o_, g_ = layer(lw, h, mask, prev, None if routing is None else routing[i])
        del lw
        used.append(u_), own.append(o_), band.append(g_)
    h = _rms(h, w["llm/norm/weight"], m["rms_norm_eps"])
    return h, jnp.stack(used), jnp.stack(own), jnp.stack(band)


# --------------------------------------------------------------------------
# the readings the comparison uses


def run(cfg: dict, data: dict, seed: int, step_rows: list, total_steps: int,
        routing: list | None = None, precision: str = "f32", fault: str | None = None) -> dict:
    """``longcat_fusion.run`` over this decoder: follow ``len(step_rows)``
    steps from the seed's weights over the given rows, the trained part through
    that file's ``make_step``. Same readings under the same names."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    w = make_weights(cfg, seed)
    step = _LC.make_step(cfg, total_steps, precision)
    tw0 = {n: w[n] for n in w if is_trained(n)}
    tw = tw0
    mu = {n: jnp.zeros_like(v) for n, v in tw0.items()}
    nu = {n: jnp.zeros_like(v) for n, v in tw0.items()}
    out: dict = {k: [] for k in ("loss", "hidden", "logits", "routing", "routing_own",
                                 "band", "real")}
    for count, rows in enumerate(step_rows):
        rows = np.asarray(rows, np.int64)
        mask = data["pad_mask"][rows]
        h, used, own, band = decoder(
            cfg, w, data["input_ids"][rows], mask,
            None if routing is None else routing[count], precision, fault)
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
        for name, value in (("routing", used), ("routing_own", own), ("band", band)):
            out[name].append(np.asarray(value))
        out["real"].append(mask)
    out["delta"] = {n: float(jnp.sqrt(jnp.sum(jnp.square(tw[n] - tw0[n])))) for n in tw0}
    out["epsilon"] = cfg["check"]["route_epsilon"]
    out["held"] = tuple(cfg["experts_held"])
    return out
