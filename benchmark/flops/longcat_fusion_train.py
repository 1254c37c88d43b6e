"""Operations a train step of the joint classifier over a *frozen*
latent-attention routed-expert decoder needs, counted from the
configuration's shapes and the window's exact counters — never from what the
compiled program does. The frozen decoder costs its forward once, over real
tokens (padding is not needed work); causal attention its lower triangle; the
held experts the assignments that really went to them (the program's own
count, ``moe_held_assignments``); the zero-compute experts nothing (a scaled
copy). The trained GGNN and head cost their forward three times.
"""

from __future__ import annotations

from harness import spec


def mla_params(c: dict) -> int:
    """One latent-attention block's projection weights."""
    h, heads = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    qr, kr = c["q_lora_rank"], c["kv_lora_rank"]
    return h * qr + qr * heads * (dn + dr) + h * (kr + dr) + kr * heads * (dn + dv) + heads * dv * h


def layer_token_params(c: dict) -> int:
    """Weights every real token passes through in one layer: two attention
    blocks, two dense FFNs, the router over its published width."""
    h = c["hidden_size"]
    router = h * (c["published"]["n_routed_experts"] + c["zero_expert_num"])
    return 2 * mla_params(c) + 2 * 3 * h * c["ffn_hidden_size"] + router


def count(cfg: dict, c: dict) -> int:
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    layers = cfg["num_layers"]
    total = 2 * layers * layer_token_params(cfg) * c["tokens_real"]
    # scores and values over the keys at or before each query: len^2 / 2 pairs a row
    per_pair = 2 * heads * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    total += layers * 2 * per_pair * c["tokens_sq"] // 2
    total += 2 * 3 * h * cfg["expert_ffn_hidden_size"] * c.get("moe_held_assignments", 0)
    head_in = h
    if cfg["use_gnn"]:
        g = cfg["gnn"]
        head_in += 2 * g["hidden_dim"] * (4 if g.get("concat_all_absdf", True) else 1)
        ggnn = spec.load_module("flops", "roberta_fusion_train").ggnn_forward_flops
        total += 3 * ggnn(g, c["graph_nodes_real"], c["graph_edges_real"])
    total += 6 * (head_in * h + h * 2) * c["functions"]
    return total
