"""Operations a train step of the joint classifier over a *frozen* hybrid
state-space / attention decoder needs, counted from the configuration's
shapes and the window's exact counters — never from what the compiled program
does. The frozen decoder costs its forward once, over real tokens (padding is
not needed work): twice the matrices a token passes through, and the
attention layers' causal lower triangle. The trained GGNN and head cost their
forward three times. The selective scan's own work (elementwise, on the VPU
and EUP, no product the MXU runs) is **not** in ``count``: ``step_mfu.train``
stays a share of the matrix peak; ``scan_ops`` and ``scan_bytes`` are the
operation and byte counts of one Mamba layer's scan for a
``selective_scan_roofline`` reader to come (PERF.md section 7).
"""

from __future__ import annotations

from harness import spec


def mamba_token_params(c: dict) -> int:
    """Matrix weights every token passes through in a Mamba mixer."""
    h, di = c["hidden_size"], c["mamba_expand"] * c["hidden_size"]
    r, n = c["mamba_dt_rank"], c["mamba_d_state"]
    return h * 2 * di + di * (r + 2 * n) + r * di + di * h


def attention_token_params(c: dict) -> int:
    """Matrix weights every token passes through in an attention mixer."""
    h = c["hidden_size"]
    kv = c["num_key_value_heads"] * (h // c["num_attention_heads"])
    return 2 * h * h + 2 * h * kv


def attention_layers(c: dict) -> int:
    return sum((i - c["attn_layer_offset"]) % c["attn_layer_period"] == 0
               for i in range(c["num_hidden_layers"]))


def scan_ops(cfg: dict, tokens: int) -> int:
    """Elementwise operations of one Mamba layer's selective scan over
    ``tokens`` computed positions. Per (token, channel, state) 7: ``delta *
    A``, its ``exp`` (the one transcendental), ``* s``, ``x * B``, the add,
    ``s * C`` and the add of the state reduction. Per (token, channel) 8 more:
    ``x = delta * c``, ``D * c`` and its add, the gate ``y * silu(z)`` (``silu``
    as exp, add, divide, multiply)."""
    di = cfg["mamba_expand"] * cfg["hidden_size"]
    return tokens * di * (7 * cfg["mamba_d_state"] + 8)


def scan_bytes(cfg: dict, tokens: int) -> int:
    """Bytes one Mamba layer's scan has to move: ``c``, ``delta`` and ``z``
    read and ``y`` written at ``[tokens, d_inner]`` bfloat16, ``B`` and ``C``
    read at ``[tokens, d_state]`` bfloat16, ``A`` and ``D`` in float32. The
    state never leaves the chip's fast memory in a scan that is bound by
    these."""
    di, n = cfg["mamba_expand"] * cfg["hidden_size"], cfg["mamba_d_state"]
    return tokens * (4 * di + 2 * n) * 2 + (di * n + di) * 4


def count(cfg: dict, c: dict) -> int:
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    layers, n_attn = cfg["num_hidden_layers"], attention_layers(cfg)
    per_token = ((layers - n_attn) * mamba_token_params(cfg)
                 + n_attn * attention_token_params(cfg)
                 + layers * 3 * h * cfg["intermediate_size"])
    total = 2 * per_token * c["tokens_real"]
    # scores and values over the keys at or before each query: len^2 / 2 pairs a row
    per_pair = 2 * heads * 2 * (h // heads)
    total += n_attn * per_pair * c["tokens_sq"] // 2
    head_in = h
    if cfg["use_gnn"]:
        g = cfg["gnn"]
        head_in += 2 * g["hidden_dim"] * (4 if g.get("concat_all_absdf", True) else 1)
        ggnn = spec.load_module("flops", "roberta_fusion_train").ggnn_forward_flops
        total += 3 * ggnn(g, c["graph_nodes_real"], c["graph_edges_real"])
    total += 6 * (head_in * h + h * 2) * c["functions"]
    return total
