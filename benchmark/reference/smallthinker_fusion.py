"""Plain reference: MSIVD's joint classifier over a frozen grouped-query
decoder whose layers differ by kind of attention and whose router reads the
layer's input (the SmallThinker layer) — the decoder's final-norm hidden
states pooled at the last real token, joined with the *trained* GGNN's graph
embedding, a 2-way head. Serves every configuration whose file says
``"reference": "smallthinker_fusion"``.

The layer, as computed here (input ``x`` [tokens, hidden]; RMSNorm ``N``, eps
from the configuration; no biases)::

    n = N_in(x)
    l = n W_r                       [experts]                  the router reads the attention's INPUT
    c = top-k of l ; g = softmax(l[c])
    q, k, v = n W_q [heads x d], n W_k [kv heads x d], n W_v [kv heads x d]
    rope_layout[i] = 1: q, k rotated: (x1, x2) = the two halves of d,
            (x1 cos - x2 sin, x2 cos + x1 sin), angle = p * theta^(-2j/d), p counting the row's REAL tokens from 0
    sliding_window_layout[i] = 1: key j visible to query t iff t - window < j <= t ; else iff j <= t ; pads are no keys
    a = x + softmax(q k^T / sqrt(d) + mask) v W_o              query head h reads key/value head h // (heads / kv heads)
    m = N_post(a)
    y = a + sum over (e in c and held) of g_e ( relu(m W_gate_e) * (m W_up_e) ) W_down_e

Departures from the published code are the configuration file's ``assumed``.

Written in straightforward ``jax.numpy``, float32, ``Precision.HIGHEST``: no
kernel, no grouped product; experts as a loop over the held ones with masks
over all tokens; attention with the scores whole, one row and one key/value
head's query heads at a time (28 heads' scores of one 8,192-token row are 7.5
GB). The trained part — GGNN over each row's own graph, head, loss, clip,
AdamW — *is* ``reference/longcat_fusion.py``'s, imported, as are the lazy
per-leaf weights. It imports nothing of ``deepdfa_tpu``. One layer's weights
are on the chip at a time.

**Routing under rounding** is that file's rule with the router's logits ``l``
in the place of its ``p + b``: ``run`` takes the program's choices
(``routing``) at a token-layer only where the experts the two sides disagree
on span a band of the reference's own logits narrower than
``check.route_epsilon``; a swap it takes moves all k gates through the softmax
over the chosen, as it does in the program.

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
COMPARISON = "frozen_train_steps"
SUBKEYS = _LC.SUBKEYS
HI = lax.Precision.HIGHEST
ROUND = _LC.ROUND
seed_key = _LC.seed_key
is_trained = _LC.is_trained
_mm, _rms = _LC._mm, _LC._rms

FAULTS = (
    "half_batch", "state_unchanged", "window_dropped", "rope_on_global", "rope_dropped",
    "router_reads_m", "silu_for_relu", "sigmoid_gates", "expert_skipped", "softmax_all",
)


# --------------------------------------------------------------------------
# weights


def model_of(cfg: dict) -> dict:
    """The decoder's sizes: the published keys at the top level of the
    configuration file, the experts held beside the router's width."""
    m = {k: cfg[k] for k in (
        "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "moe_ffn_hidden_size", "moe_num_primary_experts",
        "moe_num_active_primary_experts", "sliding_window_size", "rms_norm_eps", "rope_theta")}
    assert cfg["moe_primary_router_apply_softmax"] and cfg["norm_topk_prob"]
    assert cfg["rope_scaling"] is None
    m["rope"] = tuple(cfg["rope_layout"][:m["num_hidden_layers"]])
    m["windowed"] = tuple(cfg["sliding_window_layout"][:m["num_hidden_layers"]])
    m["lo"], hi = cfg["experts_held"]
    m["n_held"] = hi - m["lo"]
    return m


def leaf_specs(cfg: dict) -> dict[str, tuple[tuple[int, ...], str, float]]:
    """name -> (shape, kind, std), kinds as ``longcat_fusion.leaf_specs`` has
    them. Names are the program's tree paths. Every kernel is made at
    1/sqrt(fan_in): queries and keys then have unit-variance entries and the
    scores, over sqrt(d), unit spread as made."""
    m = model_of(cfg)
    h, f, d = m["hidden_size"], m["moe_ffn_hidden_size"], m["head_dim"]
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
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
        out[f"{p}/moe/router_kernel"] = (
            (h, m["moe_num_primary_experts"]), "normal16", 1.0 / math.sqrt(h))
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
    """``longcat_fusion.Weights`` over this decoder's leaves."""

    def __init__(self, cfg: dict, seed: int):
        self.specs, self.key = leaf_specs(cfg), seed_key(seed)


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


def _attention(m, rnd, w, rope, window, x, mask):
    """One row: x [s, hidden], mask [s] -> [s, hidden]; ``rope`` whether q
    and k are rotated, ``window`` the band or None."""
    s = x.shape[0]
    heads, kv, d = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    rep = heads // kv
    q = _mm(rnd, x, w["q_proj/kernel"]).reshape(s, heads, d)
    k = _mm(rnd, x, w["k_proj/kernel"]).reshape(s, kv, d)
    v = _mm(rnd, x, w["v_proj/kernel"]).reshape(s, kv, d)
    if rope:
        real = jnp.maximum(jnp.cumsum(mask) - 1, 0).astype(jnp.float32)  # the first real token is 0
        q, k = _rope(q, real, m["rope_theta"]), _rope(k, real, m["rope_theta"])
    t = jnp.arange(s)
    ok = (t[None, :] <= t[:, None]) & mask[None, :]
    if window is not None:
        ok &= t[None, :] > t[:, None] - window

    def group(qkv):  # one key/value head and the query heads that read it
        qg, kg, vg = qkv  # [rep, s, d], [s, d], [s, d]
        scores = jnp.einsum("hqd,kd->hqk", rnd(qg), rnd(kg), precision=HI) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(ok[None], scores, -1e30), axis=-1)
        probs = jnp.where(jnp.any(ok, -1)[None, :, None], probs, 0.0)  # a pad attends to nothing
        return jnp.einsum("hqk,kd->hqd", rnd(probs), rnd(vg), precision=HI)

    ctx = lax.map(group, (q.reshape(s, kv, rep, d).transpose(1, 2, 0, 3),
                          k.transpose(1, 0, 2), v.transpose(1, 0, 2)))  # [kv, rep, s, d]
    return _mm(rnd, ctx.transpose(2, 0, 1, 3).reshape(s, heads * d), w["o_proj/kernel"])


def _moe(m, rnd, fault, eps_route, w, n, u, real, prog_choice):
    """n (the router's input), u (the experts') [t, hidden], real [t] ->
    (out [t, hidden], used, own, band)."""
    k, lo, n_held = m["moe_num_active_primary_experts"], m["lo"], m["n_held"]
    logits = jnp.matmul(u if fault == "router_reads_m" else n, w["router_kernel"],
                        precision=HI)  # never rounded
    _, own = lax.top_k(logits, k)
    if prog_choice is None:
        used, band = own, jnp.zeros(u.shape[0], jnp.float32)
    else:
        # the experts the two disagree on, by the reference's own logits: the
        # highest it chose and the program left out, the lowest the program
        # chose in its place (equal sets: band 0)
        theirs = jnp.take_along_axis(logits, jnp.maximum(prog_choice, 0), axis=-1)
        theirs = jnp.where(prog_choice >= 0, theirs, -jnp.inf)  # a pad token's -1
        left_out = ~jnp.any(own[:, :, None] == prog_choice[:, None, :], -1)
        mine = jnp.take_along_axis(logits, own, axis=-1)
        band = jnp.max(jnp.where(left_out, mine, -jnp.inf), -1) - jnp.min(theirs, -1)
        band = jnp.where(jnp.any(left_out, -1) & real, band, 0.0)
        used = jnp.where((band < eps_route)[:, None], prog_choice, own)
    chosen = jnp.take_along_axis(logits, used, axis=-1)
    if fault == "sigmoid_gates":
        g = jax.nn.sigmoid(chosen)
        g = g / jnp.sum(g, -1, keepdims=True)
    elif fault == "softmax_all":  # over all the experts, the chosen's sum left as it falls
        g = jnp.take_along_axis(jax.nn.softmax(logits, -1), used, axis=-1)
    else:
        g = jax.nn.softmax(chosen, -1)
    held = (used >= lo) & (used < lo + n_held) & real[:, None]
    act = jax.nn.silu if fault == "silu_for_relu" else jax.nn.relu

    def expert(out, ew):  # every token through every held expert, masked by its gate
        e, gate, up, down = ew
        ge = jnp.sum(jnp.where(held & (used == lo + e), g, 0.0), -1, keepdims=True)
        if fault == "expert_skipped":
            ge = ge * (e != n_held // 2)
        return out + ge * _mm(rnd, act(_mm(rnd, u, gate)) * _mm(rnd, u, up), down), None

    out, _ = lax.scan(expert, jnp.zeros_like(u), (
        jnp.arange(n_held), w["experts_gate"], w["experts_up"], w["experts_down"]))
    return out, used, own, band


def _layer(m, precision, fault, eps_route, rope, windowed, lw, h, mask, prog_choice):
    """One layer over the batch: h [b, s, hidden], mask [b, s]; ``rope`` and
    ``windowed`` are this layer's entries of the two layouts."""
    rnd = ROUND[precision]
    b, s, hid = h.shape
    eps = m["rms_norm_eps"]
    sub = lambda prefix: {k[len(prefix) + 1:]: v for k, v in lw.items()
                          if k.startswith(prefix + "/")}
    if fault == "rope_on_global":
        rope = True
    elif fault == "rope_dropped":
        rope = False
    window = m["sliding_window_size"] if windowed and fault != "window_dropped" else None
    n = _rms(h, lw["input_norm/weight"], eps)
    a = h + lax.map(lambda xm: _attention(m, rnd, sub("attn"), rope, window, *xm), (n, mask))
    u = _rms(a, lw["post_attn_norm/weight"], eps)
    out, used, own, band = _moe(
        m, rnd, fault, eps_route, sub("moe"), n.reshape(b * s, hid), u.reshape(b * s, hid),
        mask.reshape(b * s), None if prog_choice is None else prog_choice.reshape(b * s, -1))
    k = used.shape[-1]
    return (a + out.reshape(b, s, hid), used.reshape(b, s, k), own.reshape(b, s, k),
            band.reshape(b, s))


def decoder(cfg: dict, w: Weights, ids, mask, routing=None, precision="f32", fault=None):
    """Final-norm hidden states [b, s, hidden] and, per layer, the choices
    used, the reference's own, and the bands ([layers, b, s, ...])."""
    m = model_of(cfg)
    eps_route = cfg["check"]["route_epsilon"]
    h = w["llm/embed_tokens/embedding"][ids]
    used, own, band = [], [], []
    for i in range(m["num_hidden_layers"]):
        kind = (bool(m["rope"][i]), bool(m["windowed"][i]))
        layer = _BASE._memo(
            lambda: jax.jit(partial(_layer, m, precision, fault, eps_route, *kind)),
            "smallthinker_layer", cfg, precision, fault, kind, routing is None)
        lw = w.under(f"llm/layers_{i}")  # this layer's alone
        h, u_, o_, g_ = layer(lw, h, mask, None if routing is None else routing[i])
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
