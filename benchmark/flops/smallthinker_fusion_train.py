"""Operations a train step of the joint classifier over a *frozen*
grouped-query decoder of global and windowed layers with routed experts needs,
counted from the configuration's shapes and the window's exact counters —
never from what the compiled program does. The frozen decoder costs its
forward once, over real tokens (padding is not needed work): twice the
matrices every token passes through (the four attention projections and the
router), the query-key pairs a layer needs — a real query's real keys at or
before it, inside the window where the layer has one (the driver's
``attn_pairs_global`` / ``attn_pairs_window``, from the rows' real lengths) —
and the assignments that really went to held experts (the program's own count,
``moe_held_assignments``). The trained GGNN and head cost their forward three
times. ``attention_ops`` and ``attention_bytes`` are the operation and byte
counts of one layer's attention kernel (``ops/gqa_attention.py``, event
``gqa_attention_fwd``) for a ``gqa_attention_roofline`` reader to come
(PERF.md section 7 row 9).
"""

from __future__ import annotations

from harness import spec


def attention_token_params(c: dict) -> int:
    """Matrix weights every token passes through in a layer's attention."""
    h, d = c["hidden_size"], c["head_dim"]
    return 2 * h * c["num_attention_heads"] * d + 2 * h * c["num_key_value_heads"] * d


def layer_token_params(c: dict) -> int:
    """Weights every real token passes through in a layer outside the experts:
    attention and the router over all its experts."""
    return attention_token_params(c) + c["hidden_size"] * c["moe_num_primary_experts"]


def window_layers(c: dict) -> int:
    return sum(c["sliding_window_layout"][:c["num_hidden_layers"]])


def row_pairs(length: int, window: int | None) -> int:
    """Query-key pairs one layer needs for a row of ``length`` real tokens:
    each query its keys at or before it, the last ``window`` of them at most."""
    if window is None or length <= window:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def attention_ops(cfg: dict, pairs: int) -> int:
    """Operations one layer's attention needs for ``pairs`` query-key pairs a
    head (``row_pairs`` summed over the rows): the score and the value
    product, 2 x 128 multiply-adds each, every query head."""
    return 2 * cfg["num_attention_heads"] * 2 * cfg["head_dim"] * pairs


def attention_bytes(cfg: dict, tokens: int) -> int:
    """Bytes one layer's attention has to move for ``tokens`` real positions:
    q read and o written at the query heads' width, k and v read once at the
    key/value heads', bfloat16. The scores never leave the chip's fast memory
    in a kernel that is bound by these."""
    width = 2 * cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"]
    return tokens * width * cfg["head_dim"] * 2


def count(cfg: dict, c: dict) -> int:
    h, layers, n_win = cfg["hidden_size"], cfg["num_hidden_layers"], window_layers(cfg)
    total = 2 * layers * layer_token_params(cfg) * c["tokens_real"]
    per_pair = 2 * cfg["num_attention_heads"] * 2 * cfg["head_dim"]  # scores and values
    total += per_pair * ((layers - n_win) * c["attn_pairs_global"]
                         + n_win * c["attn_pairs_window"])
    total += 2 * 3 * h * cfg["moe_ffn_hidden_size"] * c.get("moe_held_assignments", 0)
    head_in = h
    if cfg["use_gnn"]:
        g = cfg["gnn"]
        head_in += 2 * g["hidden_dim"] * (4 if g.get("concat_all_absdf", True) else 1)
        ggnn = spec.load_module("flops", "roberta_fusion_train").ggnn_forward_flops
        total += 3 * ggnn(g, c["graph_nodes_real"], c["graph_edges_real"])
    total += 6 * (head_in * h + h * 2) * c["functions"]
    return total
