"""Generator ``text_graphs``: a text mix joined row by row with a graph mix."""

from __future__ import annotations

from harness import traffic


def generate(params: dict, seed: int) -> dict:
    """Example ``i`` of the text mix with graph ``i`` of the graph mix: the
    same function, so one label. The two mixes are named, not repeated."""
    n = params["n_examples"]
    text = traffic.generate(params["text"], seed, {"n_examples": n})
    graphs = traffic.generate(params["graphs"], seed, {"n_graphs": n}, labels=text["labels"])
    return {**text, "graphs": graphs}
