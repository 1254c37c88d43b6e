"""Operations a train step of the RoBERTa-family encoder with the optional
GGNN branch *needs*, counted from the configuration's shapes and the window's
exact counters — never from what the compiled program does, so the
count does not move when the program changes. Recomputation, padding rows,
padded tokens and padded graph nodes are not needed work and are not counted.

A matrix multiply of ``[m, k] x [k, n]`` is ``2 m k n``; a trained weight costs
its forward three times (forward, gradient of the input, gradient of the
weight); a frozen branch with no trained weight upstream costs its forward once.
"""

from __future__ import annotations


def encoder_matmul_params(m: dict) -> int:
    """Weights of the matmuls every token passes through, all layers: Q, K, V
    and output projections and the two feed-forward matrices (no embeddings,
    biases or norms: those are gathers and element-wise work)."""
    h, ff = m["hidden_size"], m["intermediate_size"]
    return m["num_hidden_layers"] * (4 * h * h + 2 * h * ff)


def ggnn_forward_flops(g: dict, nodes: int, edges: int) -> int:
    """Embedding concat is a gather. Per round: edge Linear and the two GRU
    projections per node, one add of ``width`` per edge; then the pooling gate
    and the weighted sum."""
    width = g["hidden_dim"] * (4 if g.get("concat_all_absdf", True) else 1)
    per_round = nodes * (2 * width * width + 2 * 2 * width * 3 * width) + edges * width
    return g["n_steps"] * per_round + nodes * 2 * (2 * 2 * width)


def count(cfg: dict, c: dict) -> int:
    """Needed FLOPs of the steps counted in ``c`` (a driver's counters):
    ``6 x matmul weights x real tokens`` for the encoder, attention's two
    ``len x len x hidden`` products per row and layer (x3 for the backward),
    the head on one vector a row, and the frozen GGNN's forward over the real
    nodes and edges."""
    m = cfg["model"]
    h = m["hidden_size"]
    total = 6 * encoder_matmul_params(m) * c["tokens_real"]
    total += 3 * m["num_hidden_layers"] * 2 * 2 * h * c["tokens_sq"]
    head_in = h
    if cfg["use_gnn"]:
        g = cfg["gnn"]
        head_in += 2 * g["hidden_dim"] * (4 if g.get("concat_all_absdf", True) else 1)
        mult = 1 if cfg["freeze_gnn"] else 3
        total += mult * ggnn_forward_flops(g, c["graph_nodes_real"], c["graph_edges_real"])
    total += 6 * (head_in * h + h * 2) * c["functions"]
    return total

