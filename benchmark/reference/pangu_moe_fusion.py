"""Plain reference: MSIVD's joint classifier over a frozen sandwich-norm,
latent-attention decoder whose layers differ by FFN kind (the
openPangu-Ultra-MoE layer) — the decoder's final-norm hidden states pooled at
the last real token, joined with the *trained* GGNN's graph embedding, a 2-way
head. Serves every configuration whose file says ``"reference":
"pangu_moe_fusion"``.

The layer, as computed here (input ``h`` [tokens, hidden]; RMSNorm ``N``, eps
from the configuration; no biases)::

    a  = h + N_post_attn( MLA( N_in(h) ) )
    h' = a + N_post_mlp( F( N_pre_mlp(a) ) )        F = DenseFFN for layer < first_k_dense_replace, else MoE
    MLA(x): c_q = N(W_qa x) ; q = W_qb c_q -> heads x (nope | rope)              (no latent scales)
            [c_kv | k_r] = W_kva x ; c_kv = N(c_kv) ; [k_n | v] = W_kvb c_kv -> heads x (nope | v)
            RoPE(theta) on q's rope part and on k_r, interleaved pairs (2i, 2i+1), k_r shared by all heads
            scores = (q_n.k_n + q_r.k_r) / sqrt(nope + rope), causal, pad-masked
            out = W_o concat_heads(softmax(scores) v)
    MoE(u): s = sigmoid(W_r u) over the routed experts ; choice = top-k of s
            g = scaling * s[choice] / (sum s[choice] + 1e-20)     # over all k chosen, held or not
            E, Shared = W_down(silu(W_gate u) * (W_up u)), expert width
    here:   Shared(u) + sum over (choice and held) of g_e E_e(u)

What the absent experts would add is left out, as in the program, and the
partial ``h`` goes on. Departures from the published code are the
configuration file's ``assumed``.

Written in straightforward ``jax.numpy``, float32, ``Precision.HIGHEST``: no
kernels, experts as a loop over the held ones with masks over all tokens,
attention with the scores whole, one row and one group of heads at a time
(``reference/longcat_fusion.py``'s own latent attention, called on a group's
columns of ``W_qb`` / ``W_kvb`` and rows of ``W_o``: a head enters the result
through its own rows alone, and 128 heads' scores of one row are 2.1 GB). The
trained part — GGNN over each row's own graph, head, loss, clip, AdamW — *is*
that file's, imported, as are the lazy per-leaf weights. It imports nothing of
``deepdfa_tpu``. One layer's weights are on the chip at a time.

**Routing under rounding** is that file's rule with the router's scores ``s``
in the place of its ``p + b``: ``run`` takes the program's choices
(``routing``, one entry an *expert* layer) at a token-layer only where the
experts the two sides disagree on span a band of the reference's own scores
narrower than ``check.route_epsilon``; a swap it takes moves all k gates
through the renormalisation, as it does in the program.

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
_mm, _rms, _ffn = _LC._mm, _LC._rms, _LC._ffn

FAULTS = (
    "half_batch", "state_unchanged", "shared_skipped", "not_renormalised", "scaling_one",
    "softmax_scores", "post_norm_skipped", "dense_as_experts", "expert_skipped",
)
HEAD_GROUP = 32  # heads whose scores are live together


# --------------------------------------------------------------------------
# weights


def model_of(cfg: dict) -> dict:
    """The decoder's sizes: the published keys at the top level of the
    configuration file, the router's published width beside the experts held."""
    m = {k: cfg[k] for k in (
        "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_hidden_layers", "first_k_dense_replace", "num_attention_heads", "kv_lora_rank",
        "q_lora_rank", "qk_rope_head_dim", "v_head_dim", "qk_nope_head_dim",
        "routed_scaling_factor", "n_shared_experts", "num_experts_per_tok", "rms_norm_eps",
        "rope_theta")}
    assert cfg["norm_topk_prob"] and cfg["sandwich_norm"]
    m["mla_scale_q_lora"] = m["mla_scale_kv_lora"] = False  # ``_LC._mla``'s switches
    m["n_held"] = cfg["n_routed_experts"]
    m["n_routed"] = cfg["published"]["n_routed_experts"]
    m["lo"] = cfg["experts_held"][0]
    assert cfg["experts_held"][1] - m["lo"] == m["n_held"]
    return m


def leaf_specs(cfg: dict) -> dict[str, tuple[tuple[int, ...], str, float]]:
    """name -> (shape, kind, std), kinds as ``longcat_fusion.leaf_specs``
    has them. Names are the program's tree paths. Every kernel is made at
    1/sqrt(fan_in): with no latent scales the attention scores have unit
    spread as made, and every branch leaves through a norm."""
    m = model_of(cfg)
    h, ff, f = m["hidden_size"], m["intermediate_size"], m["moe_intermediate_size"]
    heads, dn, dr, dv = (m["num_attention_heads"], m["qk_nope_head_dim"],
                         m["qk_rope_head_dim"], m["v_head_dim"])
    qr, kr = m["q_lora_rank"], m["kv_lora_rank"]
    out: dict[str, tuple[tuple[int, ...], str, float]] = {}

    def kernel(name, fan_in, fan_out):
        out[f"{name}/kernel"] = ((fan_in, fan_out), "normal16", 1.0 / math.sqrt(fan_in))

    def norm(name, n):
        out[f"{name}/weight"] = ((n,), "ones16", 0.02)

    def gated(name, width):
        kernel(f"{name}/gate_proj", h, width)
        kernel(f"{name}/up_proj", h, width)
        kernel(f"{name}/down_proj", width, h)

    out["llm/embed_tokens/embedding"] = ((m["vocab_size"], h), "normal16", 1.0)
    for i in range(m["num_hidden_layers"]):
        p = f"llm/layers_{i}"
        a = f"{p}/attn"
        for name in ("input_norm", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm"):
            norm(f"{p}/{name}", h)
        kernel(f"{a}/q_a_proj", h, qr)
        norm(f"{a}/q_a_norm", qr)
        kernel(f"{a}/q_b_proj", qr, heads * (dn + dr))
        kernel(f"{a}/kv_a_proj", h, kr + dr)
        norm(f"{a}/kv_a_norm", kr)
        kernel(f"{a}/kv_b_proj", kr, heads * (dn + dv))
        kernel(f"{a}/o_proj", heads * dv, h)
        if i < m["first_k_dense_replace"]:
            gated(f"{p}/ffn", ff)
            continue
        out[f"{p}/moe/router_kernel"] = ((h, m["n_routed"]), "normal16", 1.0 / math.sqrt(h))
        gated(f"{p}/moe/shared_expert", m["n_shared_experts"] * f)
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


def _mla(m, rnd, w, x, mask):
    """One row: ``longcat_fusion._mla`` a group of heads at a time, summed
    (each head enters ``W_o``'s product through its own rows)."""
    heads, dn, dr, dv = (m["num_attention_heads"], m["qk_nope_head_dim"],
                         m["qk_rope_head_dim"], m["v_head_dim"])
    g = HEAD_GROUP if heads % HEAD_GROUP == 0 else heads
    out = jnp.zeros_like(x)
    for lo in range(0, heads, g):
        cols = lambda name, d: w[name].reshape(-1, heads, d)[:, lo:lo + g].reshape(-1, g * d)
        group = {**w, "q_b_proj/kernel": cols("q_b_proj/kernel", dn + dr),
                 "kv_b_proj/kernel": cols("kv_b_proj/kernel", dn + dv),
                 "o_proj/kernel": w["o_proj/kernel"].reshape(heads, dv, -1)[lo:lo + g].reshape(
                     g * dv, -1)}
        out = out + _LC._mla({**m, "num_attention_heads": g}, rnd, None, group, x, mask)
    return out


def _moe(m, rnd, fault, eps_route, w, u, real, prog_choice):
    """u [t, hidden], real [t] -> (out [t, hidden], used, own, band)."""
    k, lo, n_held = m["num_experts_per_tok"], m["lo"], m["n_held"]
    logits = jnp.matmul(u, w["router_kernel"], precision=HI)  # never rounded
    score = jax.nn.softmax(logits, -1) if fault == "softmax_scores" else jax.nn.sigmoid(logits)
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
    g = jnp.take_along_axis(score, used, axis=-1)
    if fault != "not_renormalised":
        g = g / (jnp.sum(g, -1, keepdims=True) + 1e-20)
    g = (1.0 if fault == "scaling_one" else m["routed_scaling_factor"]) * g * real[:, None]
    shared = {n[len("shared_expert/"):]: v for n, v in w.items() if n.startswith("shared_expert/")}
    out = jnp.zeros_like(u)
    if fault != "shared_skipped":
        out = _ffn(rnd, u, shared["gate_proj/kernel"], shared["up_proj/kernel"],
                   shared["down_proj/kernel"])
    held = (used >= lo) & (used < lo + n_held) & real[:, None]
    for e in range(n_held):  # every token through every held expert, masked by its gate
        if fault == "expert_skipped" and e == n_held // 2:
            continue
        ge = jnp.sum(jnp.where(held & (used == lo + e), g, 0.0), -1, keepdims=True)
        out = out + ge * _ffn(rnd, u, w["experts_gate"][e], w["experts_up"][e],
                              w["experts_down"][e])
    return out, used, own, band


def _layer(m, precision, fault, eps_route, dense, lw, h, mask, prog_choice):
    """One layer over the batch: h [b, s, hidden], mask [b, s]. ``dense``
    says which FFN; a dense layer returns no routing."""
    rnd = ROUND[precision]
    b, s, hid = h.shape
    eps = m["rms_norm_eps"]
    sub = lambda prefix: {k[len(prefix) + 1:]: v for k, v in lw.items()
                          if k.startswith(prefix + "/")}
    x = _rms(h, lw["input_norm/weight"], eps)
    attn = lax.map(lambda xm: _mla(m, rnd, sub("attn"), *xm), (x, mask))
    if fault != "post_norm_skipped":
        attn = _rms(attn, lw["post_attn_norm/weight"], eps)
    a = h + attn
    u = _rms(a, lw["pre_mlp_norm/weight"], eps)
    used = own = band = None
    if dense:
        f = sub("ffn")
        out = _ffn(rnd, u, f["gate_proj/kernel"], f["up_proj/kernel"], f["down_proj/kernel"])
    else:
        out, used, own, band = _moe(
            m, rnd, fault, eps_route, sub("moe"), u.reshape(b * s, hid), mask.reshape(b * s),
            None if prog_choice is None else prog_choice.reshape(b * s, -1))
        k = used.shape[-1]
        out, used, own, band = (out.reshape(b, s, hid), used.reshape(b, s, k),
                                own.reshape(b, s, k), band.reshape(b, s))
    return a + _rms(out, lw["post_mlp_norm/weight"], eps), used, own, band


def decoder(cfg: dict, w: Weights, ids, mask, routing=None, precision="f32", fault=None):
    """Final-norm hidden states [b, s, hidden] and, per *expert* layer, the
    choices used, the reference's own, and the bands ([expert layers, b, s,
    ...]). ``dense_as_experts`` builds the leading layers as expert layers
    over the first expert layer's weights; their routing is not handed out."""
    m = model_of(cfg)
    eps_route, first = cfg["check"]["route_epsilon"], m["first_k_dense_replace"]
    h = w["llm/embed_tokens/embedding"][ids]
    used, own, band = [], [], []
    for i in range(m["num_hidden_layers"]):
        lw = w.under(f"llm/layers_{i}")  # this layer's alone
        dense = i < first and fault != "dense_as_experts"
        if i < first and not dense:
            lw.update({f"moe/{n}": v for n, v in w.under(f"llm/layers_{first}/moe").items()})
        choice = None if routing is None or i < first else routing[i - first]
        layer = _BASE._memo(
            lambda: jax.jit(partial(_layer, m, precision, fault, eps_route, dense)),
            "pangu_layer", cfg, precision, fault, dense, choice is None)
        h, u_, o_, g_ = layer(lw, h, mask, choice)
        del lw
        if i >= first:
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
