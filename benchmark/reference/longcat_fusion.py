"""Plain reference: MSIVD's joint classifier over a frozen latent-attention,
routed-expert decoder (the LongCat-Flash layer) — the decoder's final-norm
hidden states pooled at the last real token, joined with the *trained* GGNN's
graph embedding, a 2-way head. Serves every configuration whose file says
``"reference": "longcat_fusion"``.

The layer, as computed here (input ``h`` [tokens, hidden]; RMSNorm eps from
the configuration; no biases)::

    for i in (0, 1):
        a = h + MLA_i(RMSNorm(h))
        u = RMSNorm(a)
        if i == 0: s = MoE(u)                  # the shortcut
        h = a + W_down_i(silu(W_gate_i u) * (W_up_i u))
        if i == 1: h = h + s
    MLA(x): c_q = RMSNorm(W_qa x) * sqrt(hidden / q_lora_rank)
            q = W_qb c_q -> heads x (nope | rope)
            [c_kv | k_r] = W_kva x ; c_kv = RMSNorm(c_kv) * sqrt(hidden / kv_lora_rank)
            [k_n | v] = W_kvb c_kv -> heads x (nope | v) ; k_r shared by all heads
            RoPE(theta) on q's rope part and on k_r, interleaved pairs (2i, 2i+1)
            scores = (q_n.k_n + q_r.k_r) / sqrt(nope + rope), causal, pad-masked
            out = W_o concat_heads(softmax(scores) v)
    MoE(u): p = softmax(W_r u) over routed + zero experts ; choice = top-k of (p + b)
            g = scaling * p[choice]            # not renormalised
            E_e(u) = W_down_e(silu(W_gate_e u) * (W_up_e u)), e < routed ; E_e(u) = u else
    here:   sum over (choice and held) of g_e E_e(u) + sum over (choice, e >= routed) of g_e u

What the absent experts would add is left out, as in the program, and the
partial ``h`` goes on. Departures from the published code are the
configuration file's ``assumed`` (order inside the double layer, where the
two latent scales apply, the rope pairing, no renormalisation, the bias in the
choice only, an identity expert scaled by its gate alone).

Written in straightforward ``jax.numpy``, float32, ``Precision.HIGHEST``: no
kernels, experts as a loop over the held ones with masks over all tokens,
attention with the scores whole, one row at a time; the GGNN over each row's
own graph (padded to a power of two with masks, so that one traced program
serves every size) and the head follow ``reference/roberta_fusion.py``, here
trained: forward, loss, gradients, global-norm clip, AdamW. It imports nothing
of ``deepdfa_tpu``. One layer's weights are on the chip at a time.

Weights are made **per leaf from (seed, leaf name) on the device**
(:class:`Weights`, a lazy mapping): decoder leaves are bfloat16-representable
float32 values, the trained leaves plain float32.

**Routing under rounding.** With random weights the k-th and (k+1)-th of the
router's scores are near, and bfloat16 upstream of the float32 router flips
that choice for some tokens; program and reference then differ there by a
whole expert's output. ``run`` therefore takes the program's choices
(``routing``) and uses them at a token-layer **only where rounding explains
them**: every expert the two sides disagree on — those the reference chose
and the program did not, and the reverse — scores, by the reference's own
``p + b``, within one band narrower than ``check.route_epsilon`` (the band
straddles the cut between the k-th and the (k+1)-th score). Everywhere else
it keeps its own choice. Its own choices and each token-layer's band (0
where the two agree) are returned beside, so the comparison holds **every**
real token-layer to the rule: a router that is wrong anywhere swaps experts
whose scores lie further apart than rounding moves them, and that
token-layer counts against ``route_gap``.

``precision="fp8"`` is the control: every matmul operand of the forward pass
rounded to float8_e4m3 (per-tensor scale), the nearest precision below the
bfloat16 the configuration states. ``fault=`` plants one fault (``FAULTS``).
"""

from __future__ import annotations

import math
import zlib
from collections.abc import Mapping
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from harness import spec
from jax import lax

_BASE = spec.load_module("reference", "roberta_fusion")  # seed_key, rounding, schedule
COMPARISON = "frozen_train_steps"
SUBKEYS = _BASE.SUBKEYS
HI = lax.Precision.HIGHEST
ROUND = _BASE.ROUND
seed_key = _BASE.seed_key

FAULTS = (
    "half_batch", "state_unchanged", "no_shortcut", "zero_experts_return_0", "bias_ignored",
    "renormalised", "expert_skipped", "capacity_limit", "no_kv_scale", "no_rope_scores",
    "bias_ignored_sparse",
)
SPARSE = 32  # ``bias_ignored_sparse``: the router is wrong at one token in this many


# --------------------------------------------------------------------------
# weights


def model_of(cfg: dict) -> dict:
    """The decoder's sizes: the published keys at the top level of the
    configuration file, the router's published width beside the experts held."""
    m = {k: cfg[k] for k in (
        "vocab_size", "hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size", "num_layers",
        "num_attention_heads", "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim", "v_head_dim",
        "qk_nope_head_dim", "mla_scale_q_lora", "mla_scale_kv_lora", "routed_scaling_factor",
        "zero_expert_num", "moe_topk", "rms_norm_eps", "rope_theta")}
    m["n_held"] = cfg["n_routed_experts"]
    m["n_routed"] = cfg["published"]["n_routed_experts"]
    m["lo"] = cfg["experts_held"][0]
    assert cfg["experts_held"][1] - m["lo"] == m["n_held"]
    return m


def leaf_specs(cfg: dict) -> dict[str, tuple[tuple[int, ...], str, float]]:
    """name -> (shape, kind, std). Kinds: ``normal`` (0, std); ``ones``
    (1 + normal * std: a norm's scale); a ``16`` suffix marks a decoder leaf,
    whose values are rounded to bfloat16. Names are the program's tree paths."""
    m = model_of(cfg)
    h, ff, f = m["hidden_size"], m["ffn_hidden_size"], m["expert_ffn_hidden_size"]
    heads, dn, dr, dv = (m["num_attention_heads"], m["qk_nope_head_dim"],
                         m["qk_rope_head_dim"], m["v_head_dim"])
    qr, kr = m["q_lora_rank"], m["kv_lora_rank"]
    width = m["n_routed"] + m["zero_expert_num"]
    out: dict[str, tuple[tuple[int, ...], str, float]] = {}

    def kernel(name, fan_in, fan_out):
        out[f"{name}/kernel"] = ((fan_in, fan_out), "normal16", 1.0 / math.sqrt(fan_in))

    def norm(name, n):
        out[f"{name}/weight"] = ((n,), "ones16", 0.02)

    # queries scaled so that the attention scores have about unit spread, as a
    # trained model's do: at 1/sqrt(fan_in) the two latent scales make them
    # spread by 5.8, attention picks one key, and rounding upstream then moves
    # the pick — bfloat16 and float32 part by a tenth within two layers
    sq = math.sqrt(h / qr) if m["mla_scale_q_lora"] else 1.0
    skv = math.sqrt(h / kr) if m["mla_scale_kv_lora"] else 1.0
    unit_scores = math.sqrt((dn + dr) / (dn * (sq * skv) ** 2 + dr * sq ** 2))
    out["llm/embed_tokens/embedding"] = ((m["vocab_size"], h), "normal16", 1.0)
    for i in range(m["num_layers"]):
        p = f"llm/layers_{i}"
        for j in (0, 1):
            a = f"{p}/attn_{j}"
            norm(f"{p}/attn_norm_{j}", h)
            kernel(f"{a}/q_a_proj", h, qr)
            norm(f"{a}/q_a_norm", qr)
            out[f"{a}/q_b_proj/kernel"] = (
                (qr, heads * (dn + dr)), "normal16", unit_scores / math.sqrt(qr))
            kernel(f"{a}/kv_a_proj", h, kr + dr)
            norm(f"{a}/kv_a_norm", kr)
            kernel(f"{a}/kv_b_proj", kr, heads * (dn + dv))
            kernel(f"{a}/o_proj", heads * dv, h)
            norm(f"{p}/ffn_norm_{j}", h)
            kernel(f"{p}/ffn_{j}/gate_proj", h, ff)
            kernel(f"{p}/ffn_{j}/up_proj", h, ff)
            kernel(f"{p}/ffn_{j}/down_proj", ff, h)
        out[f"{p}/moe/router_kernel"] = ((h, width), "normal16", 1.0 / math.sqrt(h))
        # small against the chosen probabilities (about 5 / width at the cut),
        # so that it moves choices without deciding them
        out[f"{p}/moe/router_bias"] = ((width,), "normal16", 0.4 / width)
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


@partial(jax.jit, static_argnames=("shape", "kind"))
def _leaf(key, std, *, shape, kind):
    z = jax.random.normal(key, shape, jnp.float32) * std
    if kind.startswith("ones"):
        z = 1.0 + z
    return z.astype(jnp.bfloat16).astype(jnp.float32) if kind.endswith("16") else z


class Weights(Mapping):
    """``{name: float32 array on the device}``, each leaf made when asked for
    from ``(seed, name)`` alone — never the 5 billion numbers at once."""

    def __init__(self, cfg: dict, seed: int):
        self.specs, self.key = leaf_specs(cfg), seed_key(seed)

    def __getitem__(self, name: str) -> jax.Array:
        shape, kind, std = self.specs[name]
        key = jax.random.fold_in(self.key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        return _leaf(key, std, shape=shape, kind=kind)

    def __iter__(self):
        return iter(self.specs)

    def __len__(self):
        return len(self.specs)

    def under(self, prefix: str) -> dict[str, jax.Array]:
        """The leaves under ``prefix/`` by their names below it."""
        return {n[len(prefix) + 1:]: self[n] for n in self.specs if n.startswith(prefix + "/")}


def make_weights(cfg: dict, seed: int) -> Weights:
    return Weights(cfg, seed)


def is_trained(name: str) -> bool:
    return name.startswith("fusion/")


# --------------------------------------------------------------------------
# the decoder


def _mm(rnd, x, w):
    return jnp.matmul(rnd(x), rnd(w), precision=HI)


def _rms(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * weight


def _rope(x, pos, theta):
    """Interleaved pairs: (x[2i], x[2i+1]) turned by pos * theta^(-2i/d)."""
    d = x.shape[-1]
    ang = pos[:, None] * (1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    cos, sin = jnp.cos(ang), jnp.sin(ang)  # [s, d/2]
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(x.shape)


def _mla(m, rnd, fault, w, x, mask):
    """One row: x [s, hidden], mask [s] -> [s, hidden]."""
    s, hid = x.shape
    heads, dn, dr, dv = (m["num_attention_heads"], m["qk_nope_head_dim"],
                         m["qk_rope_head_dim"], m["v_head_dim"])
    eps, kr = m["rms_norm_eps"], m["kv_lora_rank"]
    scale_q = math.sqrt(hid / m["q_lora_rank"]) if m["mla_scale_q_lora"] else 1.0
    scale_kv = math.sqrt(hid / kr) if m["mla_scale_kv_lora"] and fault != "no_kv_scale" else 1.0
    pos = jnp.arange(s, dtype=jnp.float32)
    c_q = _rms(_mm(rnd, x, w["q_a_proj/kernel"]), w["q_a_norm/weight"], eps) * scale_q
    q = _mm(rnd, c_q, w["q_b_proj/kernel"]).reshape(s, heads, dn + dr)
    ckv = _mm(rnd, x, w["kv_a_proj/kernel"])
    c_kv = _rms(ckv[:, :kr], w["kv_a_norm/weight"], eps) * scale_kv
    kv = _mm(rnd, c_kv, w["kv_b_proj/kernel"]).reshape(s, heads, dn + dv)
    q_r = _rope(q[..., dn:], pos, m["rope_theta"])
    k_r = _rope(ckv[:, kr:], pos, m["rope_theta"])  # [s, dr], every head's
    scores = jnp.einsum("qhd,khd->hqk", rnd(q[..., :dn]), rnd(kv[..., :dn]), precision=HI)
    if fault != "no_rope_scores":
        scores = scores + jnp.einsum("qhd,kd->hqk", rnd(q_r), rnd(k_r), precision=HI)
    scores = scores / math.sqrt(dn + dr)
    ok = (pos[None, :] <= pos[:, None]) & mask[None, :]
    probs = jax.nn.softmax(jnp.where(ok[None], scores, -1e30), axis=-1)
    probs = jnp.where(jnp.any(ok, -1)[None, :, None], probs, 0.0)  # a pad row attends to nothing
    ctx = jnp.einsum("hqk,khd->qhd", rnd(probs), rnd(kv[..., dn:]), precision=HI)
    return _mm(rnd, ctx.reshape(s, heads * dv), w["o_proj/kernel"])


def _ffn(rnd, x, gate, up, down):
    return _mm(rnd, jax.nn.silu(_mm(rnd, x, gate)) * _mm(rnd, x, up), down)


def _moe(m, rnd, fault, eps_route, w, u, real, prog_choice):
    """u [t, hidden], real [t] -> (out [t, hidden], used, own, band)."""
    k, n_routed, lo, n_held = m["moe_topk"], m["n_routed"], m["lo"], m["n_held"]
    p = jax.nn.softmax(jnp.matmul(u, w["router_kernel"], precision=HI), axis=-1)  # never rounded
    score = p if fault == "bias_ignored" else p + w["router_bias"]
    if fault == "bias_ignored_sparse":
        score = jnp.where((jnp.arange(u.shape[0]) % SPARSE == 0)[:, None], p, score)
    _, own = lax.top_k(score, k)
    if prog_choice is None:
        used, band = own, jnp.zeros(u.shape[0], jnp.float32)
    else:
        # the experts the two disagree on, by the reference's own scores: the
        # highest it chose and the program left out, the lowest the program
        # chose in its place (equal sets: band 0)
        theirs = jnp.take_along_axis(score, jnp.maximum(prog_choice, 0), axis=-1)
        theirs = jnp.where(prog_choice >= 0, theirs, -jnp.inf)  # a pad token's -1
        left_out = ~jnp.any(own[:, :, None] == prog_choice[:, None, :], -1)
        mine = jnp.take_along_axis(score, own, axis=-1)
        band = jnp.max(jnp.where(left_out, mine, -jnp.inf), -1) - jnp.min(theirs, -1)
        band = jnp.where(jnp.any(left_out, -1) & real, band, 0.0)
        used = jnp.where((band < eps_route)[:, None], prog_choice, own)
    g = jnp.take_along_axis(p, used, axis=-1)
    if fault == "renormalised":
        g = g / jnp.sum(g, -1, keepdims=True)
    g = m["routed_scaling_factor"] * g * real[:, None]
    out = jnp.zeros_like(u)
    if fault != "zero_experts_return_0":
        out = out + jnp.sum(jnp.where(used >= n_routed, g, 0.0), -1, keepdims=True) * u
    held = (used >= lo) & (used < lo + n_held) & real[:, None]
    for e in range(n_held):  # every token through every held expert, masked by its gate
        if fault == "expert_skipped" and e == n_held // 2:
            continue
        mine = held & (used == lo + e)
        if fault == "capacity_limit":  # 1.25 x the mean load, first come; the overflow is dropped
            cap = -(-5 * jnp.sum(held) // (4 * n_held))
            rank = jnp.cumsum(jnp.any(mine, -1)) - 1
            mine = mine & (rank < cap)[:, None]
        ge = jnp.sum(jnp.where(mine, g, 0.0), -1, keepdims=True)
        out = out + ge * _ffn(rnd, u, w["experts_gate"][e], w["experts_up"][e],
                              w["experts_down"][e])
    return out, used, own, band


def _layer(m, precision, fault, eps_route, lw, h, mask, prog_choice):
    """One double layer over the batch: h [b, s, hidden], mask [b, s]."""
    rnd = ROUND[precision]
    b, s, hid = h.shape
    eps = m["rms_norm_eps"]
    sub = lambda prefix: {k[len(prefix) + 1:]: v for k, v in lw.items()
                          if k.startswith(prefix + "/")}
    for i in (0, 1):
        x = _rms(h, lw[f"attn_norm_{i}/weight"], eps)
        a = h + lax.map(lambda xm: _mla(m, rnd, fault, sub(f"attn_{i}"), *xm), (x, mask))
        u = _rms(a, lw[f"ffn_norm_{i}/weight"], eps)
        if i == 0:
            short, used, own, band = _moe(
                m, rnd, fault, eps_route, sub("moe"), u.reshape(b * s, hid),
                mask.reshape(b * s), None if prog_choice is None
                else prog_choice.reshape(b * s, -1))
        f = sub(f"ffn_{i}")
        h = a + _ffn(rnd, u, f["gate_proj/kernel"], f["up_proj/kernel"], f["down_proj/kernel"])
        if i == 1 and fault != "no_shortcut":
            h = h + short.reshape(b, s, hid)
    k = used.shape[-1]
    return h, used.reshape(b, s, k), own.reshape(b, s, k), band.reshape(b, s)


def decoder(cfg: dict, w: Weights, ids, mask, routing=None, precision="f32", fault=None):
    """Final-norm hidden states [b, s, hidden] and, per layer, the choices
    used, the reference's own, and the bands ([layers, b, s, ...])."""
    m = model_of(cfg)
    eps_route = cfg["check"]["route_epsilon"]
    layer = _BASE._memo(
        lambda: jax.jit(partial(_layer, m, precision, fault, eps_route)),
        "longcat_layer", cfg, precision, fault, routing is None)
    h = w["llm/embed_tokens/embedding"][ids]
    used, own, band = [], [], []
    for i in range(m["num_layers"]):
        lw = w.under(f"llm/layers_{i}")  # this layer's alone
        h, u_, o_, g_ = layer(lw, h, mask, None if routing is None else routing[i])
        del lw
        used.append(u_), own.append(o_), band.append(g_)
    h = _rms(h, w["llm/norm/weight"], m["rms_norm_eps"])
    return h, jnp.stack(used), jnp.stack(own), jnp.stack(band)


# --------------------------------------------------------------------------
# the trained part: GGNN over each row's own graph, head, loss, optimizer


def _pow2(n: int, least: int = 64) -> int:
    return max(least, 1 << (int(n) - 1).bit_length())


def pad_graphs(data: dict, rows) -> dict:
    """The rows' graphs, each padded to the batch's largest power of two:
    feature ids 0, edges pointing past the last node (dropped), ``n`` real."""
    gs = [_BASE.graph_of(data["graphs"], int(i)) for i in rows]
    n = np.array([g["node_feats"]["_ABS_DATAFLOW_api"].shape[0] for g in gs], np.int32)
    n_pad = _pow2(n.max())
    e_pad = _pow2(max(len(g["senders"]) for g in gs))
    feats = {sk: np.zeros((len(gs), n_pad), np.int32) for sk in SUBKEYS}
    snd = np.zeros((len(gs), e_pad), np.int32)
    rcv = np.full((len(gs), e_pad), n_pad, np.int32)
    for r, g in enumerate(gs):
        for sk in SUBKEYS:
            feats[sk][r, :n[r]] = g["node_feats"][f"_ABS_DATAFLOW_{sk}"]
        snd[r, :len(g["senders"])] = g["senders"]
        rcv[r, :len(g["receivers"])] = g["receivers"]
    return {"feats": feats, "senders": snd, "receivers": rcv, "n": n}


def _ggnn_embed(cfg, rnd, w, g):
    """One graph -> pooled embedding [2 * width], as roberta_fusion.ggnn_embed."""
    f = "fusion/flowgnn_encoder"
    mm = lambda a, name: _mm(rnd, a, w[f"{f}/{name}/kernel"]) + w[f"{f}/{name}/bias"]
    x = jnp.concatenate([w[f"{f}/embed_{sk}/embedding"][g["feats"][sk]] for sk in SUBKEYS], 1)
    h, width = x, x.shape[1]
    for _ in range(cfg["gnn"]["n_steps"]):
        msg = mm(h, "ggnn/edge_linear")
        agg = jnp.zeros_like(h).at[g["receivers"]].add(msg[g["senders"]], mode="drop")
        xp, hp = mm(agg, "ggnn/gru/x_proj"), mm(h, "ggnn/gru/h_proj")
        r = jax.nn.sigmoid(xp[:, :width] + hp[:, :width])
        z = jax.nn.sigmoid(xp[:, width:2 * width] + hp[:, width:2 * width])
        n = jnp.tanh(xp[:, 2 * width:] + r * hp[:, 2 * width:])
        h = (1.0 - z) * n + z * h
    out = jnp.concatenate([h, x], 1)
    gate = jnp.where(jnp.arange(x.shape[0]) < g["n"], mm(out, "pooling/gate")[:, 0], -jnp.inf)
    return jnp.sum(jax.nn.softmax(gate)[:, None] * out, 0)


def loss_fn(cfg, precision, tw, pooled, graphs, labels, weight):
    """Mean cross-entropy over the rows with ``weight`` 1; ``pooled`` is the
    frozen decoder's state at each row's last real token."""
    rnd = ROUND[precision]
    x = pooled
    if cfg["use_gnn"]:
        x = jnp.concatenate([x, jax.vmap(partial(_ggnn_embed, cfg, rnd, tw))(graphs)], -1)
    dense = lambda name, a: (_mm(rnd, a, tw[f"fusion/classifier/{name}/kernel"])
                             + tw[f"fusion/classifier/{name}/bias"])
    logits = dense("out_proj", jnp.tanh(dense("dense", x)))
    ce = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1), labels[:, None], 1)[:, 0]
    return jnp.sum(ce * weight) / jnp.maximum(jnp.sum(weight), 1.0), logits


def make_step(cfg: dict, total_steps: int, precision: str):
    """jitted ``(tw, mu, nu, count, pooled, graphs, labels, weight) -> (tw, mu,
    nu, loss, logits, grad_norms)``: clip by global norm, AdamW, warm-up then
    cosine, as ``roberta_fusion.make_step``; every leaf of ``tw`` is trained."""
    t = cfg["train"]

    def step(tw, mu, nu, count, pooled, graphs, labels, weight):
        (loss, logits), g = jax.value_and_grad(
            lambda tw_: loss_fn(cfg, precision, tw_, pooled, graphs, labels, weight),
            has_aux=True)(tw)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
        clip = jnp.minimum(1.0, t["max_grad_norm"] / jnp.maximum(gnorm, 1e-30))
        g = {n: x * clip for n, x in g.items()}
        k = count + 1
        lr = _BASE.learning_rate(cfg, total_steps, count)
        new_w, new_mu, new_nu = {}, {}, {}
        for n in tw:
            new_mu[n] = t["adam_b1"] * mu[n] + (1 - t["adam_b1"]) * g[n]
            new_nu[n] = t["adam_b2"] * nu[n] + (1 - t["adam_b2"]) * jnp.square(g[n])
            upd = (new_mu[n] / (1 - t["adam_b1"] ** k)) / (
                jnp.sqrt(new_nu[n] / (1 - t["adam_b2"] ** k)) + t["adam_epsilon"])
            if t["weight_decay"] and not _BASE._no_decay(n):
                upd = upd + t["weight_decay"] * tw[n]
            new_w[n] = tw[n] - lr * upd
        norms = {n: jnp.sqrt(jnp.sum(jnp.square(x))) for n, x in g.items()}
        return new_w, new_mu, new_nu, loss, logits, norms

    return _BASE._memo(lambda: jax.jit(step), "longcat_step", cfg, total_steps, precision)


# --------------------------------------------------------------------------
# the readings the comparison uses


def run(cfg: dict, data: dict, seed: int, step_rows: list, total_steps: int,
        routing: list | None = None, precision: str = "f32", fault: str | None = None) -> dict:
    """Follow ``len(step_rows)`` steps from the seed's weights over the given
    rows. Returns ``loss``, ``grad1``, ``delta`` (per trained leaf, as
    ``roberta_fusion.run``) and, per step: ``hidden`` (final-norm states of
    the real tokens, [n_real, hidden]), ``logits``, ``routing`` (the choices
    used), ``routing_own``, ``band`` (module docstring) and ``real`` (the pad
    mask); ``epsilon`` and ``held`` repeat the configuration's
    ``route_epsilon`` and ``experts_held`` for the comparison."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    w = make_weights(cfg, seed)
    step = make_step(cfg, total_steps, precision)
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
        graphs = pad_graphs(data, rows) if cfg["use_gnn"] else None
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
