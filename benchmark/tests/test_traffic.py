import numpy as np
from harness import traffic

BIG = 2**31 + 12345


def test_same_seed_same_inputs_and_big_seeds_work():
    a = traffic.generate("tiny-text-graphs", BIG, {"n_examples": 300})
    b = traffic.generate("tiny-text-graphs", BIG, {"n_examples": 300})
    assert np.array_equal(a["input_ids"], b["input_ids"])
    assert np.array_equal(a["graphs"]["senders"], b["graphs"]["senders"])
    c = traffic.generate("tiny-text-graphs", BIG + 1, {"n_examples": 300})
    assert not np.array_equal(a["input_ids"], c["input_ids"])
    # the seed changes contents, never sizes or which functions are vulnerable
    assert np.array_equal(a["labels"], c["labels"]) and 0 < a["labels"].mean() < 0.2
    assert np.array_equal(a["lengths"], c["lengths"])
    assert np.array_equal(a["graphs"]["n_nodes"], c["graphs"]["n_nodes"])
    assert np.array_equal(a["labels"], a["graphs"]["labels"])


def test_text_layout_is_left_padded_roberta():
    mix = traffic.load_mix("tiny-text")
    sp = mix["special"]
    d = traffic.generate("tiny-text", 3, {"n_examples": 200})
    ids, mask, n = d["input_ids"], d["pad_mask"], d["lengths"]
    assert ids.shape == (200, mix["block"]) and (mask.sum(1) == n).all()
    assert (ids[~mask] == sp["pad"]).all() and (ids[:, -1] == sp["eos"]).all()
    first = mix["block"] - n
    assert (ids[np.arange(200), first] == sp["bos"]).all()
    assert (mask[np.arange(200), first]).all() and n.min() >= mix["length"]["min"]
    body = ids[mask]
    assert body.max() < mix["vocab"]


def test_graphs_are_chains_with_shortcuts_and_self_loops():
    g = traffic.generate("tiny-graphs", 5, {"n_graphs": 150})
    for i in range(150):
        n = int(g["n_nodes"][i])
        e0, e1 = g["edge_off"][i], g["edge_off"][i + 1]
        s, r = g["senders"][e0:e1], g["receivers"][e0:e1]
        assert e1 - e0 == (n - 1) + max(1, n // 8) + n
        assert s.min() >= 0 and max(s.max(), r.max()) < n
        assert (s[: n - 1] + 1 == r[: n - 1]).all()          # the chain
        assert (s[-n:] == np.arange(n)).all() and (r[-n:] == s[-n:]).all()  # self-loops
        x = slice(n - 1, e1 - e0 - n)
        assert ((r[x] - s[x] >= 0) & (r[x] - s[x] <= 4)).all()
    feats = g["node_feats"]
    vul = np.maximum.reduceat(feats["_VULN"], g["node_off"][:-1])
    assert np.array_equal(vul, g["labels"])
    assert feats["_ABS_DATAFLOW_api"].max() < traffic.load_mix("tiny-graphs")["input_dim"]


def test_real_mixes_reach_every_serve_bucket():
    mix = traffic.load_mix("bigvul-graphs")
    n = traffic.sizes(mix["nodes"], mix["n_graphs"], mix["size_seed"])
    assert n.min() >= 3 and n.max() <= 4094
    assert (n <= 126).any() and ((n > 126) & (n <= 1022)).any() and (n > 1022).any()
    # the fusion configuration's one static GraphJoin budget holds the worst batch
    import json
    from conftest import BENCH
    cfg = json.loads((BENCH / "configs" / "linevul-fusion.json").read_text())
    b = cfg["train"]["train_batch_size"]
    assert b * int(n.max()) + 1 <= cfg["graph_join"]["max_nodes"] - 1
    edges = (n - 1) + np.maximum(1, n // 8) + n
    assert b * int(edges.max()) <= cfg["graph_join"]["max_edges"]


def test_checked_batches_of_the_real_mix_hold_no_vulnerable_function():
    """The steps ``correct`` follows must not be ones whose rows' gradients can
    cancel in the batch mean: under the configurations' shuffle seed the first
    three batches of epoch 0 are all of one label (traffic.py's docstring)."""
    import json
    from conftest import BENCH
    train = json.loads((BENCH / "configs" / "linevul.json").read_text())["train"]
    check = json.loads((BENCH / "configs" / "linevul.json").read_text())["check"]
    for name in ("bigvul-text", "bigvul-text-graphs"):
        mix = traffic.load_mix(name)
        text = traffic.load_mix(mix["text"]) if "text" in mix else mix
        n = mix["n_examples"]
        labels = traffic.labels(text, n)
        order = np.arange(n)
        np.random.default_rng(train["shuffle_seed"]).shuffle(order)  # text_batches, epoch 0
        assert labels[order[: check["steps"] * train["train_batch_size"]]].sum() == 0
        assert 0.05 < labels.mean() < 0.07


def test_a_mix_finds_its_generator_by_file_name(tmp_path, monkeypatch):
    """A generator is a file of its own: a mix that names one that is not
    there says so, and one that is there is used with no list to add it to."""
    import json

    import pytest
    from harness import spec

    monkeypatch.setattr(traffic, "TRAFFIC_DIR", tmp_path)
    (tmp_path / "m.json").write_text(json.dumps({"generator": "no-such-generator"}))
    with pytest.raises(FileNotFoundError, match="no-such-generator"):
        traffic.generate("m", 1)
    (tmp_path / "generators").mkdir()
    (tmp_path / "generators" / "ones.py").write_text(
        "def generate(params, seed):\n    return {'x': [seed] * params['n']}\n")
    monkeypatch.setattr(spec, "BENCH_DIR", tmp_path)
    (tmp_path / "m.json").write_text(json.dumps({"generator": "ones", "n": 3}))
    assert traffic.generate("m", 7) == {"x": [7, 7, 7]}
