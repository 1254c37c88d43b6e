"""Operations a train step of the joint classifier over a *frozen*
sandwich-norm latent-attention decoder with leading dense layers needs,
counted from the configuration's shapes and the window's exact counters —
never from what the compiled program does. The frozen decoder costs its
forward once, over real tokens (padding is not needed work); causal attention
its lower triangle; a leading layer its dense FFN; an expert layer its router,
its shared expert for every real token, and its held experts the assignments
that really went to them (the program's own count, ``moe_held_assignments``).
The trained GGNN and head cost their forward three times.
"""

from __future__ import annotations

from harness import spec

_longcat = spec.load_module("flops", "longcat_fusion_train")
mla_params = _longcat.mla_params  # one latent-attention block's projection weights


def dense_layer_token_params(c: dict) -> int:
    """Weights every real token passes through in a leading layer."""
    return mla_params(c) + 3 * c["hidden_size"] * c["intermediate_size"]


def expert_layer_token_params(c: dict) -> int:
    """Weights every real token passes through in an expert layer: attention,
    the router over its published width, the shared expert."""
    h = c["hidden_size"]
    shared = 3 * h * c["moe_intermediate_size"] * c["n_shared_experts"]
    return mla_params(c) + h * c["published"]["n_routed_experts"] + shared


def count(cfg: dict, c: dict) -> int:
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    per_token = (dense * dense_layer_token_params(cfg)
                 + (layers - dense) * expert_layer_token_params(cfg))
    total = 2 * per_token * c["tokens_real"]
    # scores and values over the keys at or before each query: len^2 / 2 pairs a row
    per_pair = 2 * heads * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    total += layers * per_pair * c["tokens_sq"] // 2
    total += 2 * 3 * h * cfg["moe_intermediate_size"] * c.get("moe_held_assignments", 0)
    head_in = h
    if cfg["use_gnn"]:
        g = cfg["gnn"]
        head_in += 2 * g["hidden_dim"] * (4 if g.get("concat_all_absdf", True) else 1)
        ggnn = spec.load_module("flops", "roberta_fusion_train").ggnn_forward_flops
        total += 3 * ggnn(g, c["graph_nodes_real"], c["graph_edges_real"])
    total += 6 * (head_in * h + h * 2) * c["functions"]
    return total
