"""Generator ``text``: token blocks of one length distribution."""

from __future__ import annotations

import numpy as np
from harness import traffic


def generate(params: dict, seed: int) -> dict:
    """Token blocks as a RoBERTa tokenizer + ``padding='max_length'`` with
    left padding would hand them over: ``[pad.., bos, tokens.., eos]``.

    Returns ``input_ids [n, block] int32``, ``pad_mask [n, block] bool`` (True
    = real token), ``lengths [n]``, ``labels [n] int32``, ``indices [n]``."""
    n, block = params["n_examples"], params["block"]
    sp = params["special"]
    rng = np.random.default_rng([seed, 1])
    lengths = traffic.sizes(params["length"], n, params["size_seed"])
    ids = rng.integers(sp["first_free"], params["vocab"], size=(n, block), dtype=np.int32)
    start = (block - lengths).astype(np.int32)
    pad_mask = np.arange(block, dtype=np.int32)[None, :] >= start[:, None]
    ids[np.arange(n), start] = sp["bos"]
    ids[:, -1] = sp["eos"]
    np.putmask(ids, ~pad_mask, np.int32(sp["pad"]))
    return {
        "input_ids": ids,
        "pad_mask": pad_mask,
        "lengths": lengths.astype(np.int64),
        "labels": traffic.labels(params, n),
        "indices": np.arange(n, dtype=np.int64),
    }
