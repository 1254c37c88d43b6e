"""The joint step's GGNN over a compact view of the one static graph budget
(``data/graphs.compact_view``, ``FusionModel._encode_compact``): every view a
batch can take gives the whole budget's pooled rows and gradients, ``init``
draws the leaves it always drew, the counts say which view ran and reach
``loss.sync``, and what bypasses the choice is lowered as a model without it.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepdfa_tpu.config import GGNNConfig
from deepdfa_tpu.data.graphs import Graph, batch_np, compact_view, view_fits
from deepdfa_tpu.llm import fusion as fusion_mod
from deepdfa_tpu.llm.fusion import ClassificationHead, FusionModel
from deepdfa_tpu.models import make_model

INPUT_DIM, HIDDEN, SLOTS = 8, 16, 4
MAX_NODES, MAX_EDGES = fusion_mod.MIN_VIEW_BUDGET, 2 * fusion_mod.MIN_VIEW_BUDGET  # the smallest budget with views
GNN = GGNNConfig(hidden_dim=8, n_steps=2)
# the views of that budget, smallest first, as (nodes, edges)
RUNGS = [(MAX_NODES // d, MAX_EDGES // d) for d in fusion_mod.VIEW_DIVISORS]
N, E = RUNGS[-1]  # the largest view: the one the counts are placed round


def _graph(n_nodes: int, n_edges: int, seed: int) -> Graph:
    """A chain with self-loops, then random edges up to ``n_edges`` exactly."""
    rng = np.random.default_rng(seed)
    chain = np.arange(n_nodes - 1, dtype=np.int32)
    loops = np.arange(n_nodes, dtype=np.int32)
    extra = n_edges - (2 * n_nodes - 1)
    assert extra >= 0
    xs, xr = (rng.integers(0, n_nodes, extra).astype(np.int32) for _ in range(2))
    return Graph(
        senders=np.concatenate([chain, loops, xs]),
        receivers=np.concatenate([chain + 1, loops, xr]),
        node_feats={f"_ABS_DATAFLOW_{k}": rng.integers(0, INPUT_DIM, n_nodes).astype(np.int32)
                    for k in ("api", "datatype", "literal", "operator")},
        gid=seed)


def _placeholder() -> Graph:
    """``GraphJoin``'s slot for an example whose graph is missing."""
    g = _graph(2, 3, 0)
    return Graph(np.zeros(0, np.int32), np.zeros(0, np.int32),
                 {k: v[:0] for k, v in g.node_feats.items()}, gid=-1)


def _batch(sizes: list, max_nodes: int = MAX_NODES, max_edges: int = MAX_EDGES) -> tuple:
    """``(BatchedGraphs, real nodes, real edges)`` of one graph a slot; a size
    of ``None`` is a missing graph."""
    graphs = [_placeholder() if s is None else _graph(*s, seed=i + 1) for i, s in enumerate(sizes)]
    batch = batch_np(graphs, SLOTS + 1, max_nodes, max_edges)
    return batch, int(batch.node_mask.sum()), int(batch.edge_mask.sum())


def _split(total_nodes: int, total_edges: int) -> list:
    """Sizes of three graphs (the fourth slot missing) with those totals."""
    nodes = [total_nodes // 3, total_nodes // 3, total_nodes - 2 * (total_nodes // 3)]
    edges = [2 * n - 1 for n in nodes]
    edges[-1] += total_edges - sum(edges)
    return [*zip(nodes, edges), None]


# name -> the slots' sizes; the counts are placed round the largest view (N, E)
CASES = {
    "well_inside_the_smallest_view": [(2, 3), (2, 4), None, (2, 3)],
    "well_inside_the_largest_view": _split(N // 2, E // 2 + 3),
    "n_minus_1_nodes_exactly": _split(N - 1, 2 * N),
    "n_nodes_one_over": _split(N, 2 * N + 2),
    "e_edges_exactly": _split(N // 2, E),
    "e_plus_1_edges": _split(N // 2, E + 1),
    "a_missing_graph": [(5, 11), None, (7, 15), (4, 9)],
    "every_slot_missing": [None] * SLOTS,
    "past_every_view": _split(N + 40, 2 * N + 90),
}


def _expected_nodes(nodes: int, edges: int) -> int:
    return next((n for n, e in RUNGS if nodes <= n - 1 and edges <= e), MAX_NODES)


@pytest.fixture(scope="module")
def model():
    fusion = FusionModel(gnn_cfg=GNN, input_dim=INPUT_DIM, llm_hidden_size=HIDDEN, pool="cls")
    hidden = jax.random.normal(jax.random.key(1), (SLOTS, 6, HIDDEN))
    batch, _, _ = _batch(CASES["a_missing_graph"])
    params = fusion.init({"params": jax.random.key(0), "dropout": jax.random.key(2)},
                         hidden, batch, deterministic=True)["params"]
    encoder = make_model(dataclasses.replace(GNN, encoder_mode=True, label_style="graph"), INPUT_DIM)
    head = ClassificationHead(hidden_size=HIDDEN, pool="cls")

    def whole(p, h, g):
        """The forward as it was: the encoder over the whole budget."""
        pooled = encoder.apply({"params": p["flowgnn_encoder"]}, g)
        return head.apply({"params": p["classifier"]}, h, pooled[:SLOTS]), pooled

    pooled_rows = jax.jit(lambda p, g: fusion.apply(
        {"params": p}, g, method=FusionModel._encode_compact, mutable=["stats"]))
    logits = jax.jit(lambda p, h, g: fusion.apply({"params": p}, h, g))
    loss = lambda f: (lambda p, h, g: (f(p, h, g) ** 2).sum())
    return dict(
        fusion=fusion, params=params, hidden=hidden, whole=jax.jit(whole), pooled=pooled_rows,
        logits=logits, grad=jax.jit(jax.grad(loss(logits))),
        whole_grad=jax.jit(jax.grad(loss(lambda p, h, g: whole(p, h, g)[0]))))


def test_the_cases_reach_every_branch():
    taken = {_expected_nodes(*_batch(sizes)[1:]) for sizes in CASES.values()}
    assert taken == {n for n, _ in RUNGS} | {MAX_NODES}
    assert [_expected_nodes(*_batch(CASES[c])[1:]) for c in (
        "n_minus_1_nodes_exactly", "n_nodes_one_over", "e_edges_exactly", "e_plus_1_edges",
    )] == [N, MAX_NODES, N, MAX_NODES]


@pytest.mark.parametrize("case", CASES)
def test_the_view_is_itself_a_batch(case):
    """What ``batch_np`` promises of a batch, of the prefix that ``view_fits``
    admits: real entries whole, pads on the sink, receivers sorted."""
    batch, nodes, edges = _batch(CASES[case])
    for n, e in RUNGS:
        assert bool(view_fits(batch, n, e)) == (nodes <= n - 1 and edges <= e)
        if not view_fits(batch, n, e):
            continue
        view = compact_view(batch, n, e)
        assert view.max_nodes == n and view.senders.shape == view.receivers.shape == (e,)
        assert view.node_mask.sum() == nodes and view.edge_mask.sum() == edges
        assert np.array_equal(view.senders[:edges], batch.senders[:edges])
        assert np.array_equal(view.receivers[:edges], batch.receivers[:edges])
        assert (view.senders[edges:] == n - 1).all() and (view.receivers[edges:] == n - 1).all()
        assert (np.diff(view.receivers) >= 0).all() and not view.node_mask[n - 1]
        assert (view.node_gidx[nodes:] == SLOTS).all() and view.max_graphs == SLOTS + 1
        for key, col in view.node_feats.items():
            assert np.array_equal(col, batch.node_feats[key][:n])


@pytest.mark.parametrize("case", CASES)
def test_every_view_gives_the_whole_budgets_forward(model, case):
    """Pooled rows bit for bit (or to 1e-6), logits with them, and the counts
    say which branch ran."""
    batch, nodes, edges = _batch(CASES[case])
    want_logits, want_pooled = model["whole"](model["params"], model["hidden"], batch)
    pooled, sown = model["pooled"](model["params"], batch)
    counts = {k: int(v) for k, v in sown["stats"]["ggnn"].items()}
    computed = _expected_nodes(nodes, edges)
    assert counts == {"nodes_real": nodes, "nodes_computed": computed,
                      "compact": int(computed < MAX_NODES)}
    assert pooled.shape == want_pooled.shape == (SLOTS + 1, want_pooled.shape[1])
    # the real graphs' rows; the sink's row pools pads and is read by no one
    np.testing.assert_allclose(pooled[:SLOTS], want_pooled[:SLOTS], rtol=0, atol=1e-6)
    np.testing.assert_allclose(
        model["logits"](model["params"], model["hidden"], batch), want_logits, rtol=0, atol=1e-6)


def test_a_budget_under_the_floor_runs_whole_and_says_so(model):
    """Half the smallest budget with views: the encoder as it was, over the
    batch as it arrives, and the counts of that."""
    batch, nodes, _ = _batch(CASES["a_missing_graph"], MAX_NODES // 2, MAX_EDGES // 2)
    _, want_pooled = model["whole"](model["params"], model["hidden"], batch)
    pooled, sown = model["pooled"](model["params"], batch)
    assert np.array_equal(pooled, want_pooled)
    assert {k: int(v) for k, v in sown["stats"]["ggnn"].items()} == {
        "nodes_real": nodes, "nodes_computed": MAX_NODES // 2, "compact": 0}


@pytest.mark.parametrize("case", CASES)
def test_every_view_gives_the_whole_budgets_gradients(model, case):
    """With the GGNN trained: each leaf's gradient to 1e-5 of its norm."""
    batch, _, _ = _batch(CASES[case])
    got = model["grad"](model["params"], model["hidden"], batch)
    want = model["whole_grad"](model["params"], model["hidden"], batch)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0], jax.tree.leaves(want)):
        scale = max(float(jnp.linalg.norm(w)), 1e-6)
        assert float(jnp.linalg.norm(g - w)) <= 1e-5 * scale, jax.tree_util.keystr(path)


class _Before(nn.Module):
    """``FusionModel`` with a GGNN as it was before the view: the same
    submodule names, the encoder called over the batch as it arrives."""

    gnn_cfg: GGNNConfig

    def setup(self):
        self.flowgnn_encoder = make_model(
            dataclasses.replace(self.gnn_cfg, encoder_mode=True, label_style="graph"), INPUT_DIM)
        self.classifier = ClassificationHead(hidden_size=HIDDEN, pool="cls")

    def __call__(self, hidden, graphs, deterministic=True, token_mask=None):
        pooled = self.flowgnn_encoder(graphs)
        return self.classifier(hidden, pooled[:hidden.shape[0]], deterministic=deterministic,
                               token_mask=token_mask)


def test_init_draws_the_tree_and_the_leaves_it_always_drew(model):
    batch, _, _ = _batch(CASES["a_missing_graph"])
    rngs = {"params": jax.random.key(0), "dropout": jax.random.key(2)}
    variables = model["fusion"].init(rngs, model["hidden"], batch, deterministic=True)
    assert set(variables) == {"params"}
    before = _Before(GNN).init(rngs, model["hidden"], batch)["params"]
    assert jax.tree.structure(variables["params"]) == jax.tree.structure(before)
    for (path, got), want in zip(
            jax.tree_util.tree_flatten_with_path(variables["params"])[0], jax.tree.leaves(before)):
        assert np.array_equal(got, want), jax.tree_util.keystr(path)


def test_an_apply_without_the_stats_collection_returns_what_it_did(model):
    """Evaluation, ``JointEngine`` and the benchmark's ``_check`` apply the
    fusion model plainly: logits alone, the values of the apply that counts."""
    batch, _, _ = _batch(CASES["well_inside_the_largest_view"])
    fusion, params, hidden = model["fusion"], model["params"], model["hidden"]
    plain = fusion.apply({"params": params}, hidden, batch)
    assert isinstance(plain, jax.Array) and plain.shape == (SLOTS, 2)
    logits, sown = fusion.apply({"params": params}, hidden, batch, mutable=["stats"])
    assert np.array_equal(logits, plain) and set(sown["stats"]) == {"ggnn"}
    # the encoder's node-length saliency stays inside the branches: nothing
    # a caller could have read from ``FusionModel`` changes shape by batch
    _, sown = fusion.apply({"params": params}, hidden, batch, mutable=["intermediates"])
    assert not jax.tree.leaves(sown)


def _lowered(module, params, *args) -> str:
    return jax.jit(lambda p, *a: module.apply({"params": p}, *a, mutable=["stats"])).lower(
        params, *args).as_text()


def test_without_a_gnn_nothing_is_sown_and_the_program_is_the_heads(model):
    fusion = FusionModel(gnn_cfg=None, input_dim=INPUT_DIM, llm_hidden_size=HIDDEN,
                         use_gnn=False, pool="cls")
    params = fusion.init({"params": jax.random.key(0), "dropout": jax.random.key(2)},
                         model["hidden"], None)["params"]
    logits, sown = fusion.apply({"params": params}, model["hidden"], None, mutable=["stats"])
    assert sown == {} and logits.shape == (SLOTS, 2)

    class Head(nn.Module):
        def setup(self):
            self.classifier = ClassificationHead(hidden_size=HIDDEN, pool="cls")

        def __call__(self, hidden, graphs):
            return self.classifier(hidden, None)

    assert _lowered(fusion, params, model["hidden"], None) == _lowered(
        Head(), params, model["hidden"], None)


def test_a_dense_batch_sows_nothing_and_lowers_as_before(model):
    from deepdfa_tpu.data.dense import batch_dense

    cfg = dataclasses.replace(GNN, layout="dense")
    graphs = [_graph(5, 11, 1), _graph(7, 15, 2), _placeholder(), _graph(4, 9, 3)]
    dense = batch_dense(graphs, SLOTS, 8)
    fusion = FusionModel(gnn_cfg=cfg, input_dim=INPUT_DIM, llm_hidden_size=HIDDEN, pool="cls")
    # one parameter tree in every layout
    logits, sown = fusion.apply({"params": model["params"]}, model["hidden"], dense,
                                mutable=["stats"])
    assert sown == {} and np.isfinite(logits).all()
    assert _lowered(fusion, model["params"], model["hidden"], dense) == _lowered(
        _Before(cfg), model["params"], model["hidden"], dense)


# -- the counts on ``loss.sync`` -----------------------------------------------


def _train(llm, llm_params, train_llm: bool, pool: str, hidden_size: int, vocab: int):
    """Two steps of ``JointTrainer.train`` over a joined batch of four; the
    run's ``loss.sync`` spans and the join's budget."""
    from deepdfa_tpu.data.synthetic import random_dataset
    from deepdfa_tpu.llm.dataset import GraphJoin, HashTokenizer, encode_functions
    from deepdfa_tpu.llm.joint import JointConfig, JointTrainer
    from deepdfa_tpu.obs import Tracer, TrainTelemetry

    jcfg = JointConfig(block_size=32, train_batch_size=4, eval_batch_size=4, epochs=1,
                       train_llm=train_llm, use_gnn=True, first_eval_steps=100)
    graphs = random_dataset(8, seed=0, input_dim=INPUT_DIM)
    funcs = [f"int f{i}(int a) {{ return a + {i}; }}" * (1 + i % 3) for i in range(8)]
    examples = encode_functions(funcs, [i % 2 for i in range(8)], HashTokenizer(vocab_size=vocab),
                                jcfg.block_size, indices=[g.gid for g in graphs])
    fusion = FusionModel(gnn_cfg=GNN, input_dim=INPUT_DIM, llm_hidden_size=hidden_size, pool=pool)
    join = GraphJoin.from_list(graphs, max_nodes=MAX_NODES, max_edges=MAX_EDGES)
    trainer = JointTrainer(llm=llm, llm_params=llm_params, fusion=fusion, cfg=jcfg, join=join)
    trainer.telemetry = TrainTelemetry(tracer=Tracer(proc="train", max_spans=256))
    trainer.train(examples, examples)
    assert all(np.isfinite(e["train_loss"]) for e in trainer.history if "train_loss" in e)
    by_gid = {g.gid: g.n_nodes for g in graphs}
    syncs = [s for s in trainer.telemetry.tracer.spans() if s.name == "loss.sync"]
    return syncs, sum(by_gid.values()), join


def _holds_the_ggnn_counts(syncs, total_nodes: int, join, beside: dict):
    assert len(syncs) == 2
    views = [join.max_nodes // d for d in fusion_mod.VIEW_DIVISORS]
    for span in syncs:
        real = span.attrs["ggnn_nodes_real"]
        assert span.attrs["ggnn_nodes_computed"] == next(
            (n for n in views if real <= n - 1), join.max_nodes)
        assert span.attrs["ggnn_compact"] == 1 and span.attrs["reads"] == 1
        assert {k: span.attrs[k] for k in beside} == beside
    # the epoch's two batches hold every graph once
    assert sum(s.attrs["ggnn_nodes_real"] for s in syncs) == total_nodes


def test_the_counts_reach_loss_sync_beside_a_trained_encoders():
    from deepdfa_tpu.llm.roberta import RobertaEncoder, tiny_roberta

    cfg = tiny_roberta(vocab_size=256)
    enc = RobertaEncoder(cfg)
    ids = jnp.zeros((2, 32), jnp.int32)
    params = enc.init(jax.random.key(0), ids, jnp.ones(ids.shape, bool))["params"]
    syncs, total, join = _train(enc, params, True, "cls", cfg.hidden_size, cfg.vocab_size)
    _holds_the_ggnn_counts(syncs, total, join, {"attn_layers": cfg.num_hidden_layers,
                                               "attn_fused": 0})


def test_the_counts_reach_loss_sync_beside_a_frozen_decoders():
    from deepdfa_tpu.llm.jamba import JambaModel, tiny_jamba

    cfg = tiny_jamba(vocab_size=256, num_hidden_layers=4)
    llm = JambaModel(cfg)
    ids = jnp.zeros((2, 32), jnp.int32)
    params = nn.meta.unbox(llm.init(jax.random.key(0), ids, jnp.ones(ids.shape, bool))["params"])
    syncs, total, join = _train(llm, params, False, "last", cfg.hidden_size, cfg.vocab_size)
    n_attn = len(cfg.attention_layers)
    _holds_the_ggnn_counts(syncs, total, join, {
        "ssm_layers": cfg.num_hidden_layers - n_attn, "ssm_fused": 0,
        "attn_layers": n_attn, "attn_fused": 0})


def test_a_frozen_ggnn_is_held_out_of_the_backward_and_the_step_is_the_same():
    """``freeze_gnn``: the optimizer zeroes the GGNN's updates whatever its
    gradients are, so the step that never builds them lands on the state of
    the step that does — and is the smaller program."""
    from deepdfa_tpu.data.synthetic import random_dataset
    from deepdfa_tpu.llm.dataset import GraphJoin, HashTokenizer, encode_functions, text_batches
    from deepdfa_tpu.llm.joint import JointConfig, JointTrainer, make_joint_steps
    from deepdfa_tpu.llm.roberta import RobertaEncoder, tiny_roberta

    cfg = tiny_roberta(vocab_size=256)
    enc = RobertaEncoder(cfg)
    jcfg = JointConfig(block_size=32, train_batch_size=4, eval_batch_size=4, epochs=1,
                       train_llm=True, use_gnn=True, freeze_gnn=True, learning_rate=1e-2)
    graphs = random_dataset(4, seed=0, input_dim=INPUT_DIM)
    funcs = [f"int f{i}(int a) {{ return a + {i}; }}" for i in range(4)]
    examples = encode_functions(funcs, [0, 1, 0, 1], HashTokenizer(vocab_size=cfg.vocab_size),
                                jcfg.block_size, indices=[g.gid for g in graphs])
    fusion = FusionModel(gnn_cfg=GNN, input_dim=INPUT_DIM, llm_hidden_size=cfg.hidden_size,
                         pool="cls")
    ids = jnp.zeros((2, 32), jnp.int32)
    params = enc.init(jax.random.key(0), ids, jnp.ones(ids.shape, bool))["params"]
    join = GraphJoin.from_list(graphs, max_nodes=MAX_NODES, max_edges=MAX_EDGES)
    trainer = JointTrainer(llm=enc, llm_params=params, fusion=fusion, cfg=jcfg, join=join)
    batch = trainer._joined(next(text_batches(examples, jcfg.train_batch_size)))
    state = trainer._build(50, batch)

    def jitted(launch):
        return launch.__closure__[
            launch.__code__.co_freevars.index("jitted_train_step")].cell_contents

    held_out = jitted(trainer._steps[0])
    through = jitted(make_joint_steps(enc, fusion, trainer.tx, train_llm=True)[0])
    for _ in range(2):  # the second step runs past the warm-up's lr 0
        new, loss, _, _ = held_out(state, None, batch)
        want, want_loss, _, _ = through(state, None, batch)
        assert float(loss) == float(want_loss)
        for (path, got), w in zip(jax.tree_util.tree_flatten_with_path(new.params)[0],
                                  jax.tree.leaves(want.params)):
            assert np.array_equal(got, w), jax.tree_util.keystr(path)
        state = new
    moved = jax.tree.map(lambda a, b: not np.array_equal(a, b), state.params,
                         trainer._build(50, batch).params)
    assert not any(jax.tree.leaves(moved["fusion"]["flowgnn_encoder"]))
    assert any(jax.tree.leaves(moved["fusion"]["classifier"]))
    # the lowered programs are alike (jax drops the unused backward before it
    # lowers); what is saved is the tracing of it
    conds = lambda step: sum(
        e.primitive.name == "cond" for e in step.trace(state, None, batch).jaxpr.eqns)
    assert conds(held_out) == 1 < conds(through)  # the choice, and its backward
