"""Operations a train step of the joint classifier over a *frozen* decoder of
compressed convolutional attention and top-1 MLP-routed experts needs,
counted from the configuration's shapes and the window's exact counters —
never from what the compiled program does. The frozen decoder costs its
forward once, over real tokens (padding is not needed work): twice the
matrices every token passes through (the four attention projections, the
grouped convolution's two taps, the router's down-projection and its MLP),
the query-key pairs a layer needs — a real query's real keys at or before it
(the driver's ``attn_pairs_global``, from the rows' real lengths) — and the
assignments that really went to held experts (the program's own count,
``moe_held_assignments``; the skip's cost no product). The trained GGNN and
head cost their forward three times. :func:`cca_attention_ops` and
:func:`cca_attention_bytes` are what the window's attention kernel
(``ops/gqa_attention.py``, event ``gqa_attention_fwd``) needs, every layer,
for ``cca_attn_roofline_share.train``.
"""

from __future__ import annotations

from harness import spec


def layer_token_params(c: dict) -> int:
    """Matrix weights every real token passes through in a layer outside the
    experts: q, k, v and o, the grouped convolution's two taps, the router."""
    h, d, r = c["hidden_size"], c["head_dim"], c["router_hidden_size"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    attention = 2 * h * heads * d + 2 * h * kv * d
    convolution = c["cca_time1"] * (heads + kv) * d * d
    router = h * r + 2 * r * r + r * (c["num_experts"] + 1)
    return attention + convolution + router


def pair_ops(cfg: dict) -> int:
    """Operations of one query-key pair a layer: the score and the value
    product, 2 x ``head_dim`` multiply-adds each, every query head."""
    return 2 * cfg["num_attention_heads"] * 2 * cfg["head_dim"]


def cca_attention_ops(cfg: dict, c: dict) -> int:
    """Operations the attention needs over the window, every layer: each real
    causal pair (``attn_pairs_global``) at :func:`pair_ops`."""
    return pair_ops(cfg) * cfg["num_hidden_layers"] * c["attn_pairs_global"]


def cca_attention_bytes(cfg: dict, c: dict) -> int:
    """Bytes the attention has to move over the window, every layer, at the
    positions the kernel visits (``attn_tokens_visited``): q read and o
    written at the query heads' width, k and v read at the key/value heads',
    bfloat16. The scores never leave the chip's fast memory in a kernel that
    is bound by these."""
    width = 2 * cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"]
    return width * cfg["head_dim"] * 2 * cfg["num_hidden_layers"] * c["attn_tokens_visited"]


def count(cfg: dict, c: dict) -> int:
    h = cfg["hidden_size"]
    total = 2 * cfg["num_hidden_layers"] * layer_token_params(cfg) * c["tokens_real"]
    total += cca_attention_ops(cfg, c)
    total += 2 * 3 * h * cfg["moe_intermediate_size"] * c.get("moe_held_assignments", 0)
    head_in = h
    if cfg["use_gnn"]:
        g = cfg["gnn"]
        head_in += 2 * g["hidden_dim"] * (4 if g.get("concat_all_absdf", True) else 1)
        ggnn = spec.load_module("flops", "roberta_fusion_train").ggnn_forward_flops
        total += 3 * ggnn(g, c["graph_nodes_real"], c["graph_edges_real"])
    total += 6 * (head_in * h + h * 2) * c["functions"]
    return total
