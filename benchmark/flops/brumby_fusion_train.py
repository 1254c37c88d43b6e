"""Operations a train step of the joint classifier over a *frozen* decoder
whose attention is degree-2 power retention needs, counted from the
configuration's shapes and the window's exact counters — never from what the
compiled program does. The frozen decoder costs its forward once, over real
tokens (padding is not needed work): twice the matrices every token passes
through (q, k, v, the gate, o and the MLP's three) and the retention's own
products, :func:`retention_ops`. The trained GGNN and head cost their forward
three times. :func:`retention_ops` and :func:`retention_bytes` are what one
step's retention kernel (``ops/power_retention_kernel.py``, event
``power_retention_fwd``) needs, for ``retention_roofline_share.train``.
"""

from __future__ import annotations

from harness import spec


def layer_token_params(c: dict) -> int:
    """Matrix weights every real token passes through in a layer."""
    h, d = c["hidden_size"], c["head_dim"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    return 2 * h * heads * d + 2 * h * kv * d + h * kv + 3 * h * c["intermediate_size"]


def feature_dim(d: int) -> int:
    """The symmetric square's width: the pairs ``a <= b`` of ``d`` entries."""
    return d * (d + 1) // 2


def retention_ops(cfg: dict, c: dict) -> int:
    """Operations the retention needs over the window, every layer:
    ``2 D (d + 1) (heads + kv heads)`` a real token a layer — each key/value
    head adds ``phi(k) [v | 1]^T`` to its ``[D, d + 1]`` state, each query head
    reads ``phi(q)^T [S | z]`` (``D = d (d + 1) / 2``)."""
    d = cfg["head_dim"]
    heads = cfg["num_attention_heads"] + cfg["num_key_value_heads"]
    per_token = 2 * feature_dim(d) * (d + 1) * heads
    return per_token * cfg["num_hidden_layers"] * c["tokens_real"]


def retention_bytes(cfg: dict, c: dict) -> int:
    """Bytes the retention has to move over the window, every layer, at the
    tokens it computes (``retention_tokens_visited``: the chunks that hold a
    real token): q, k and v read and o written in bfloat16, the gates read in
    float32. The state never leaves the chip's fast memory in a kernel that is
    bound by these."""
    d, heads, kv = cfg["head_dim"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    per_token = (2 * heads + 2 * kv) * d * 2 + kv * 4
    return per_token * cfg["num_hidden_layers"] * c["retention_tokens_visited"]


def count(cfg: dict, c: dict) -> int:
    h = cfg["hidden_size"]
    total = 2 * cfg["num_hidden_layers"] * layer_token_params(cfg) * c["tokens_real"]
    total += retention_ops(cfg, c)
    head_in = h
    if cfg["use_gnn"]:
        g = cfg["gnn"]
        head_in += 2 * g["hidden_dim"] * (4 if g.get("concat_all_absdf", True) else 1)
        ggnn = spec.load_module("flops", "roberta_fusion_train").ggnn_forward_flops
        total += 3 * ggnn(g, c["graph_nodes_real"], c["graph_edges_real"])
    total += 6 * (head_in * h + h * 2) * c["functions"]
    return total
