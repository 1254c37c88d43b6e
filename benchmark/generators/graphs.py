"""Generator ``graphs``: CFG-like graphs of one node-count distribution."""

from __future__ import annotations

import numpy as np
from harness import traffic

# node-feature columns every graph carries, in the order the embedding tables
# are concatenated (the abstract-dataflow subkeys) plus the combined id
SUBKEYS = ("api", "datatype", "literal", "operator")


def generate(params: dict, seed: int, labels: np.ndarray | None = None) -> dict:
    """CFG-like graphs, flat: graph ``g`` owns nodes ``node_off[g]:node_off[g+1]``
    and edges ``edge_off[g]:edge_off[g+1]`` (local node indices, self-loops
    included). The construction is ``data/synthetic.random_graph``'s — a chain,
    ``max(1, n // 8)`` forward shortcuts of 2-4 statements, one self-loop a
    node, definition nodes at ``def_rate`` carrying ids in ``[1, input_dim)``,
    1-3 vulnerable statements in a vulnerable function with their api id drawn
    from the low band — made in bulk instead of graph by graph."""
    n = params["n_graphs"]
    input_dim = params["input_dim"]
    rng = np.random.default_rng([seed, 2])
    nodes = traffic.sizes(params["nodes"], n, params["size_seed"]).astype(np.int32)
    node_off = np.concatenate([[0], np.cumsum(nodes, dtype=np.int64)])
    total = int(node_off[-1])
    gids = np.arange(n, dtype=np.int32)
    gid_of_node = np.repeat(gids, nodes)
    local = (np.arange(total, dtype=np.int64) - node_off[gid_of_node]).astype(np.int32)

    # a graph's edges, in order: its chain, its shortcuts, its self-loops
    n_extra = np.maximum(1, nodes // 8)
    edges = (nodes - 1) + n_extra + nodes
    edge_off = np.concatenate([[0], np.cumsum(edges, dtype=np.int64)])
    senders = np.empty(int(edge_off[-1]), np.int32)
    receivers = np.empty_like(senders)
    chain = local < nodes[gid_of_node] - 1
    at = (edge_off[gid_of_node] + local)[chain]
    senders[at] = local[chain]
    receivers[at] = local[chain] + 1
    gid_x = np.repeat(gids, n_extra)
    rank_x = (np.arange(gid_x.size, dtype=np.int64)
              - np.concatenate([[0], np.cumsum(n_extra, dtype=np.int64)])[gid_x])
    at = edge_off[gid_x] + (nodes[gid_x] - 1) + rank_x
    src_x = (rng.random(gid_x.size, dtype=np.float32) * (nodes[gid_x] - 1)).astype(np.int32)
    senders[at] = src_x
    receivers[at] = np.minimum(
        src_x + rng.integers(2, 5, gid_x.size, dtype=np.int32), nodes[gid_x] - 1)
    at = edge_off[gid_of_node] + (nodes + n_extra - 1)[gid_of_node] + local
    senders[at] = local
    receivers[at] = local

    is_def = rng.random(total, dtype=np.float32) < params["def_rate"]
    feats = {}
    for key in [f"_ABS_DATAFLOW_{sk}" for sk in SUBKEYS] + ["_ABS_DATAFLOW"]:
        ids = rng.integers(1, input_dim, total, dtype=np.int32)
        feats[key] = np.where(is_def, ids, 0).astype(np.int32)
    if labels is None:
        labels = traffic.labels(params, n)
    vuln = np.zeros(total, np.int32)
    vul_g = np.flatnonzero(labels)
    k = rng.integers(1, 4, vul_g.size)
    for j in range(3):  # statement j of each vulnerable function that has one
        has = vul_g[k > j]
        at = node_off[has] + (rng.random(has.size) * nodes[has]).astype(np.int64)
        vuln[at] = 1
        feats["_ABS_DATAFLOW_api"][at] = rng.integers(
            1, 1 + max(2, input_dim // 50), has.size)
    feats["_VULN"] = vuln
    return {
        "n_nodes": nodes.astype(np.int64),
        "node_off": node_off,
        "edge_off": edge_off,
        "senders": senders,
        "receivers": receivers,
        "node_feats": feats,
        "labels": labels,
        "indices": np.arange(n, dtype=np.int64),
    }
