"""Plain reference: MSIVD's joint classifier over a frozen hybrid state-space /
attention decoder (the Jamba layer) — the decoder's final-norm hidden states
pooled at the last real token, joined with the *trained* GGNN's graph
embedding, a 2-way head. Serves every configuration whose file says
``"reference": "jamba_fusion"``.

The layer, as computed here (input ``h`` [tokens, hidden]; RMSNorm ``N``, eps
from the configuration; ``m`` the pad mask as 0/1 per position; no biases but
the convolution's and ``dt``'s)::

    a  = h + Mixer_i( N_in(h) )          attention where (i - attn_layer_offset) % attn_layer_period == 0, Mamba else
    h' = a + W_down( silu(W_gate u) * (W_up u) )         u = N_ff(a)        (num_experts 1: an MLP in every layer)
    Mamba(x): [u, z] = x W_in ; u = u * m
              c_t = silu( sum_{j<k} w_j * u_{t-(k-1)+j} + b_conv ) per channel, u before position 0 is 0 ; c = c * m
              [r, B, C] = c W_x  (dt_rank | d_state | d_state) ; r = N_dt(r) ; B = N_B(B) ; C = N_C(C)
              delta = softplus(r W_dt + b_dt) ; A = -exp(A_log)
              s_t = exp(delta_t (x) A) * s_{t-1} + (delta_t * c_t) (x) B_t ,  s_{-1} = 0
              y_t = s_t . C_t + D * c_t ;  out = (y * silu(z)) W_out
    Attn(x):  q = x W_q -> heads x head_dim ; k = x W_k, v = x W_v -> ONE head of head_dim shared by all query heads
              no positional encoding ; scores / sqrt(head_dim), causal, pads masked as keys ; W_o

Written in straightforward ``jax.numpy``, float32, ``Precision.HIGHEST``: the
recurrence as a position-by-position ``lax.scan`` over a ``[rows, channels,
states]`` state, the convolution as ``k`` shifted products, attention with the
scores whole one row at a time. The trained part — GGNN over each row's own
graph, head, loss, clip, AdamW — *is* ``reference/longcat_fusion.py``'s,
imported, as is the lazy per-leaf mapping of the weights. It imports nothing
of ``deepdfa_tpu``. One layer's weights are on the chip at a time (the whole
model is 12 GB in float32).

Weights from ``(seed, leaf name)``; ``A_log``, ``D`` and ``b_dt`` are not
drawn as the matrices are but by Mamba-1's published initialisation (the
configuration file's ``assumed``): ``A_log = log(1 .. d_state)`` on every
channel, ``D = 1``, ``b_dt = softplus^-1`` of a log-uniform draw in [1e-3,
1e-1] — random ones would give decay rates no trained model has, and a scan
that forgets within a token or never.

``precision="fp8"`` is the control (every matmul operand of the forward pass
rounded to float8_e4m3); ``fault=`` plants one fault (``FAULTS``).
"""

from __future__ import annotations

import math
import zlib
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
    "half_batch", "state_unchanged", "mask_before_conv_skipped", "mask_after_conv_skipped",
    "state_bf16", "d_skip_skipped", "inner_norm_skipped", "attention_as_mamba",
    "rope_in_attention", "taps_reversed",
)
DT_MIN, DT_MAX = 1e-3, 1e-1
FLOAT32_LEAVES = ("A_log", "D", "dt_bias")  # what a bfloat16 program keeps in float32 too


# --------------------------------------------------------------------------
# weights


def model_of(cfg: dict) -> dict:
    """The decoder's sizes: the published keys at the top level of the
    configuration file."""
    m = {k: cfg[k] for k in (
        "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "attn_layer_period", "attn_layer_offset",
        "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank", "rms_norm_eps")}
    if cfg["num_experts"] != 1 or not cfg["mamba_conv_bias"] or cfg["mamba_proj_bias"]:
        raise ValueError("this reference writes the dense layer with a biased convolution "
                         "and unbiased projections only")
    m["d_inner"] = m["mamba_expand"] * m["hidden_size"]
    m["head_dim"] = m["hidden_size"] // m["num_attention_heads"]
    return m


def is_attention(m: dict, layer: int) -> bool:
    return (layer - m["attn_layer_offset"]) % m["attn_layer_period"] == 0


def leaf_specs(cfg: dict) -> dict[str, tuple[tuple[int, ...], str, float]]:
    """name -> (shape, kind, std), kinds as ``longcat_fusion.leaf_specs`` has
    them plus ``a_log`` and ``dt_bias`` (module docstring; ``D`` is a ``ones``
    of spread 0). Names are the program's tree paths. Every matrix is made at
    1/sqrt(fan_in): attention scores have unit spread as made (128 products of
    unit-variance entries over sqrt(128)) and every mixer reads a normed
    input."""
    m = model_of(cfg)
    h, ff, di = m["hidden_size"], m["intermediate_size"], m["d_inner"]
    n, k, r = m["mamba_d_state"], m["mamba_d_conv"], m["mamba_dt_rank"]
    heads, hk, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    out: dict[str, tuple[tuple[int, ...], str, float]] = {}

    def kernel(name, fan_in, fan_out):
        out[f"{name}/kernel"] = ((fan_in, fan_out), "normal16", 1.0 / math.sqrt(fan_in))

    def norm(name, width):
        out[f"{name}/weight"] = ((width,), "ones16", 0.02)

    out["llm/embed_tokens/embedding"] = ((m["vocab_size"], h), "normal16", 1.0)
    for i in range(m["num_hidden_layers"]):
        p = f"llm/layers_{i}"
        norm(f"{p}/input_norm", h)
        if is_attention(m, i):
            kernel(f"{p}/attn/q_proj", h, heads * hd)
            kernel(f"{p}/attn/k_proj", h, hk * hd)
            kernel(f"{p}/attn/v_proj", h, hk * hd)
            kernel(f"{p}/attn/o_proj", heads * hd, h)
        else:
            a = f"{p}/mamba"
            kernel(f"{a}/in_proj", h, 2 * di)
            out[f"{a}/conv_kernel"] = ((k, di), "normal16", 1.0 / math.sqrt(k))
            # the spread of the published layer's default (uniform in +-1/sqrt(k)): a bias of
            # 0.02 would make an unmasked pad's silu(b_conv) all but 0, and the mask after the
            # convolution all but idle
            out[f"{a}/conv_bias"] = ((di,), "normal16", 1.0 / math.sqrt(3 * k))
            kernel(f"{a}/x_proj", di, r + 2 * n)
            norm(f"{a}/dt_norm", r)
            norm(f"{a}/b_norm", n)
            norm(f"{a}/c_norm", n)
            kernel(f"{a}/dt_proj", r, di)
            out[f"{a}/dt_bias"] = ((di,), "dt_bias", 0.0)
            out[f"{a}/A_log"] = ((di, n), "a_log", 0.0)
            out[f"{a}/D"] = ((di,), "ones", 0.0)
            kernel(f"{a}/out_proj", di, h)
        norm(f"{p}/ffn_norm", h)
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


@partial(jax.jit, static_argnames=("shape",))
def _dt_bias(key, *, shape):
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                 * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
    return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1


class Weights(_LC.Weights):
    """``longcat_fusion.Weights`` over this decoder's leaves, with Mamba-1's
    two leaves that are not normal draws."""

    def __init__(self, cfg: dict, seed: int):
        self.specs, self.key = leaf_specs(cfg), seed_key(seed)

    def __getitem__(self, name: str) -> jax.Array:
        shape, kind, _ = self.specs[name]
        if kind == "a_log":
            return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape)
        if kind == "dt_bias":
            key = jax.random.fold_in(self.key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            return _dt_bias(key, shape=shape)
        return super().__getitem__(name)


def make_weights(cfg: dict, seed: int) -> Weights:
    return Weights(cfg, seed)


# --------------------------------------------------------------------------
# the decoder


def _mamba(m, rnd, fault, w, x, mask):
    """The batch: x [b, s, hidden], mask [b, s] -> [b, s, hidden]."""
    di, n, k, r = m["d_inner"], m["mamba_d_state"], m["mamba_d_conv"], m["mamba_dt_rank"]
    eps, s = m["rms_norm_eps"], x.shape[1]
    real = mask[..., None].astype(jnp.float32)
    uz = _mm(rnd, x, w["in_proj/kernel"])
    u, z = uz[..., :di], uz[..., di:]
    if fault != "mask_before_conv_skipped":
        u = u * real
    taps = w["conv_kernel"][::-1] if fault == "taps_reversed" else w["conv_kernel"]
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    c = w["conv_bias"] + sum(taps[j] * padded[:, j:j + s] for j in range(k))
    c = jax.nn.silu(c)
    if fault != "mask_after_conv_skipped":
        c = c * real
    rbc = _mm(rnd, c, w["x_proj/kernel"])
    normed = lambda v, name: _rms(v, w[f"{name}/weight"], eps)
    dt = normed(rbc[..., :r], "dt_norm")
    b_in = rbc[..., r:r + n] if fault == "inner_norm_skipped" else normed(rbc[..., r:r + n], "b_norm")
    c_in = normed(rbc[..., r + n:], "c_norm")
    delta = jax.nn.softplus(_mm(rnd, dt, w["dt_proj/kernel"]) + w["dt_bias"])
    a = -jnp.exp(w["A_log"])  # [channels, states]

    def step(state, inp):
        d_t, c_t, b_t, c_out = inp  # [b, channels] x 2, [b, states] x 2
        state = (jnp.exp(d_t[:, :, None] * a) * state
                 + (d_t * c_t)[:, :, None] * b_t[:, None, :])
        if fault == "state_bf16":
            state = lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)
        return state, jnp.einsum("bdn,bn->bd", state, c_out, precision=HI)

    along = lambda v: jnp.swapaxes(v, 0, 1)
    _, y = lax.scan(step, jnp.zeros((x.shape[0], di, n), jnp.float32),
                    tuple(map(along, (delta, c, b_in, c_in))))
    y = along(y)
    if fault != "d_skip_skipped":
        y = y + w["D"] * c
    return _mm(rnd, y * jax.nn.silu(z), w["out_proj/kernel"])


def _rope_half(x, pos, theta=10000.0):
    """The planted ``rope_in_attention``: rotate-half pairs ``(i, i + d/2)``."""
    d = x.shape[-1]
    ang = pos[:, None] * (1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]  # [s, 1, d/2]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attn(m, rnd, fault, w, x, mask):
    """One row: x [s, hidden], mask [s] -> [s, hidden]."""
    s = x.shape[0]
    heads, hk, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    pos = jnp.arange(s, dtype=jnp.float32)
    q = _mm(rnd, x, w["q_proj/kernel"]).reshape(s, heads, hd)
    k = _mm(rnd, x, w["k_proj/kernel"]).reshape(s, hk, hd)
    v = _mm(rnd, x, w["v_proj/kernel"]).reshape(s, hk, hd)
    if fault == "rope_in_attention":
        q, k = _rope_half(q, pos), _rope_half(k, pos)
    group = heads // hk  # query head i reads key/value head i // group
    q = q.reshape(s, hk, group, hd)
    scores = jnp.einsum("qjgd,kjd->jgqk", rnd(q), rnd(k), precision=HI) / math.sqrt(hd)
    ok = (pos[None, :] <= pos[:, None]) & mask[None, :]
    probs = jax.nn.softmax(jnp.where(ok, scores, -1e30), axis=-1)
    probs = jnp.where(jnp.any(ok, -1)[:, None], probs, 0.0)  # a pad row attends to nothing
    ctx = jnp.einsum("jgqk,kjd->qjgd", rnd(probs), rnd(v), precision=HI)
    return _mm(rnd, ctx.reshape(s, heads * hd), w["o_proj/kernel"])


def _layer(m, precision, fault, attention, lw, h, mask):
    """One layer over the batch: h [b, s, hidden], mask [b, s]."""
    rnd = ROUND[precision]
    eps = m["rms_norm_eps"]
    sub = lambda prefix: {k[len(prefix) + 1:]: v for k, v in lw.items()
                          if k.startswith(prefix + "/")}
    x = _rms(h, lw["input_norm/weight"], eps)
    if attention:
        mixed = lax.map(lambda xm: _attn(m, rnd, fault, sub("attn"), *xm), (x, mask))
    else:
        mixed = _mamba(m, rnd, fault, sub("mamba"), x, mask)
    a = h + mixed
    f = sub("mlp")
    u = _rms(a, lw["ffn_norm/weight"], eps)
    return a + _ffn(rnd, u, f["gate_proj/kernel"], f["up_proj/kernel"], f["down_proj/kernel"])


def decoder(cfg: dict, w: Weights, ids, mask, precision="f32", fault=None):
    """Final-norm hidden states [b, s, hidden]. ``attention_as_mamba`` builds
    the attention layers as Mamba layers over the preceding layer's mixer
    weights."""
    m = model_of(cfg)
    h = w["llm/embed_tokens/embedding"][ids]
    for i in range(m["num_hidden_layers"]):
        lw = w.under(f"llm/layers_{i}")  # this layer's alone
        attention = is_attention(m, i)
        if attention and fault == "attention_as_mamba":
            attention = False
            lw.update({f"mamba/{n}": v for n, v in w.under(f"llm/layers_{i - 1}/mamba").items()})
        layer = _BASE._memo(
            lambda: jax.jit(partial(_layer, m, precision, fault, attention)),
            "jamba_layer", cfg, precision, fault, attention)
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
    ``real`` (the pad mask). ``routing`` is the routed references' argument
    (a program's expert choices): this decoder routes nothing, takes none and
    hands none back, so ``tools/prove_frozen.py``'s sweep serves it as it is."""
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
