"""Fusion layer: text dataset, graph join, fusion heads, joint training.

Covers the MSIVD surface (SURVEY.md §2.2): ``TextDataset`` semantics
(``MSIVD/msivd/train.py:71-208``), the graph index-join contract
(``train.py:311-320``), ``ClassificationHead``/``GNNModel`` (``model.py``),
and the joint train loop (``train.py:211-585``).
"""

import numpy as np
import pytest

from deepdfa_tpu.config import GGNNConfig
from deepdfa_tpu.data.synthetic import random_dataset
from deepdfa_tpu.llm.dataset import (
    GraphJoin,
    HashTokenizer,
    devign_split,
    encode_functions,
    normalize_whitespace,
    text_batches,
)

INPUT_DIM = 52


def _examples(n=10, block=16, seed=0):
    rng = np.random.default_rng(seed)
    funcs = [f"int f{i}(int x) {{ return x + {i}; }}" for i in range(n)]
    labels = rng.integers(0, 2, size=n).tolist()
    return encode_functions(
        funcs, labels, HashTokenizer(vocab_size=320), block, indices=range(100, 100 + n)
    )


def test_normalize_whitespace():
    code = "int  f() {\n\n\t  return\t1;  \n}\n"
    assert normalize_whitespace(code) == "int f() {\nreturn\t1;\n}".replace("\t", " ")


def test_hash_tokenizer_block_shape_and_left_pad():
    tok = HashTokenizer(vocab_size=64)
    ids, mask = tok.encode_block("int main() { return 0; }", 32)
    assert ids.shape == (32,) and ids.dtype == np.int32
    # left padding with eos; bos where the content starts
    assert ids[0] == tok.eos_token_id
    content = ids[mask]
    assert content[0] == tok.bos_token_id
    # pad mask marks exactly the left-pad run (pads share the eos id, so the
    # mask — not the values — is the source of truth)
    assert not mask[0] and mask[-1]
    assert mask.sum() == content.shape[0]
    # truncation
    long, long_mask = tok.encode_block(" ".join(f"var{i}" for i in range(100)), 8)
    assert long.shape == (8,) and long_mask.all()


def test_hash_tokenizer_deterministic():
    tok = HashTokenizer()
    a, _ = tok.encode_block("foo barBaz", 8)
    b, _ = tok.encode_block("foo barBaz", 8)
    np.testing.assert_array_equal(a, b)


def test_encode_functions_index_join_key():
    ex = _examples(n=5)
    assert len(ex) == 5
    np.testing.assert_array_equal(ex.indices, np.arange(100, 105))
    assert ex.input_ids.shape == (5, 16)


def test_encode_functions_restores_hf_tokenizer_state():
    """encode_functions must not leak its left-pad convention into the
    caller's tokenizer (ADVICE r1)."""

    class FakeHF:
        eos_token = "</s>"
        pad_token = None
        padding_side = "right"

        def __call__(self, text, padding, truncation, max_length):
            assert self.pad_token == self.eos_token  # convention active inside
            assert self.padding_side == "left"
            return {"input_ids": [0] * max_length, "attention_mask": [1] * max_length}

    tok = FakeHF()
    ex = encode_functions(["int f();"], [0], tok, 8)
    assert ex.input_ids.shape == (1, 8)
    assert tok.pad_token is None and tok.padding_side == "right"  # restored


def test_graph_join_empty_store_raises():
    join = GraphJoin(graphs={})
    ex = _examples(n=2)
    with pytest.raises(ValueError, match="empty graph store"):
        join.join(next(text_batches(ex, 2)))


def test_devign_split_80_10_10():
    s = devign_split(100)
    assert len(s["train"]) == 80 and len(s["eval"]) == 10 and len(s["test"]) == 10
    # sequential, no shuffle (train.py:102-115)
    assert s["train"][0] == 0 and s["test"][-1] == 99


def test_text_batches_static_tail():
    ex = _examples(n=10)
    batches = list(text_batches(ex, 4))
    assert len(batches) == 3
    for b in batches:
        assert b.input_ids.shape == (4, 16)
    assert b.mask.sum() == 2  # tail batch: 2 real rows
    assert (b.indices[~b.mask] == -1).all()
    assert not b.pad_mask[~b.mask].any()  # padding rows: no real tokens


def test_graph_join_slot_alignment_and_missing():
    graphs = random_dataset(6, seed=0, input_dim=INPUT_DIM, mean_nodes=8)
    for i, g in enumerate(graphs):
        g.gid = 100 + i  # match _examples indices
    join = GraphJoin.from_list(graphs[:4], max_nodes=512, max_edges=1024)  # 104,105 missing
    ex = _examples(n=6)
    tb = next(text_batches(ex, 6))
    jb = join.join(tb)
    # examples 0..3 joined, 4..5 missing -> masked
    np.testing.assert_array_equal(jb.mask, [True] * 4 + [False] * 2)
    assert join.num_missing == 2
    # slot alignment: node counts of slots 0..3 match the graphs
    for i in range(4):
        assert (np.asarray(jb.graphs.node_gidx) == i).sum() == graphs[i].n_nodes
    # static shapes
    assert jb.graphs.max_graphs == 7


def test_fusion_head_math():
    """ClassificationHead = dropout∘dense∘tanh∘dropout∘out_proj on
    [pooled ⊕ gnn_embed] (model.py:20-29); deterministic mode == plain math.
    ``pool="first"`` is strict reference parity (the <s>-slot read)."""
    import jax
    import jax.numpy as jnp

    from deepdfa_tpu.llm.fusion import ClassificationHead

    head = ClassificationHead(hidden_size=8, dropout_rate=0.5, pool="first")
    feats = jnp.asarray(np.random.default_rng(0).normal(size=(3, 5, 8)), jnp.float32)
    embed = jnp.asarray(np.random.default_rng(1).normal(size=(3, 4)), jnp.float32)
    params = head.init(jax.random.key(0), feats, embed)["params"]
    out = head.apply({"params": params}, feats, embed)
    assert out.shape == (3, 2)

    x = np.concatenate([np.asarray(feats)[:, 0, :], np.asarray(embed)], axis=1)
    d = np.tanh(x @ np.asarray(params["dense"]["kernel"]) + np.asarray(params["dense"]["bias"]))
    expect = d @ np.asarray(params["out_proj"]["kernel"]) + np.asarray(params["out_proj"]["bias"])
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5)

    # no_flowgnn mode: embed None
    params2 = head.init(jax.random.key(0), feats, None)["params"]
    out2 = head.apply({"params": params2}, feats, None)
    assert out2.shape == (3, 2)


def test_pool_tokens_last_real_token():
    """Default pooling reads the LAST real token — position 0 of a causal LM
    is input-independent (it attends only to itself), so the reference's CLS
    read gives a constant LLM feature; 'last' is the corrected semantics."""
    import jax.numpy as jnp

    from deepdfa_tpu.llm.fusion import pool_tokens

    feats = jnp.arange(2 * 4 * 3, dtype=jnp.float32).reshape(2, 4, 3)
    # row 0: tokens at 2,3 real (left-padded); row 1: all real
    mask = jnp.asarray([[False, False, True, True], [True, True, True, True]])
    out = pool_tokens(feats, mask, "last")
    np.testing.assert_allclose(np.asarray(out), np.asarray(feats[:, -1, :]))
    # right-padded row: mask selects position 1
    mask2 = jnp.asarray([[True, True, False, False], [True, True, True, True]])
    out2 = pool_tokens(feats, mask2, "last")
    np.testing.assert_allclose(np.asarray(out2)[0], np.asarray(feats)[0, 1, :])
    # no mask: last position
    np.testing.assert_allclose(
        np.asarray(pool_tokens(feats, None, "last")), np.asarray(feats[:, -1, :])
    )


@pytest.mark.slow
def test_llm_branch_not_constant_across_inputs():
    """Regression: the pooled LLM feature must differ between two different
    functions (the slot-0 read under padding was bit-identical)."""
    import jax

    from deepdfa_tpu.llm.dataset import HashTokenizer, encode_functions
    from deepdfa_tpu.llm.fusion import pool_tokens
    from deepdfa_tpu.llm.llama import LlamaModel, tiny_llama

    llm = LlamaModel(tiny_llama(vocab_size=320))
    ex = encode_functions(
        ["void f(){ memcpy(d, s, n); }", "int g(){ return 2; }"],
        [1, 0],
        HashTokenizer(vocab_size=320),
        16,
    )
    params = llm.init(jax.random.key(0), ex.input_ids[:1])["params"]
    hidden = llm.apply({"params": params}, ex.input_ids, ex.pad_mask)
    pooled = np.asarray(pool_tokens(hidden, ex.pad_mask, "last"))
    assert not np.allclose(pooled[0], pooled[1])


def test_weight_decay_mask():
    from deepdfa_tpu.llm.joint import weight_decay_mask

    params = {
        "dense": {"kernel": np.zeros(2), "bias": np.zeros(2)},
        "input_layernorm": {"weight": np.zeros(2)},
        "gru": {"h_proj": {"kernel": np.zeros(2), "bias": np.zeros(2)}},
    }
    mask = weight_decay_mask(params)
    assert mask["dense"]["kernel"] is True
    assert mask["dense"]["bias"] is False
    assert mask["input_layernorm"]["weight"] is False
    assert mask["gru"]["h_proj"]["kernel"] is True


def test_cosine_warmup_schedule():
    from deepdfa_tpu.llm.joint import cosine_warmup_schedule

    sched = cosine_warmup_schedule(1e-3, warmup_steps=10, total_steps=100)
    assert float(sched(0)) == 0.0
    assert float(sched(10)) == pytest.approx(1e-3)
    assert float(sched(5)) == pytest.approx(5e-4)
    assert float(sched(100)) == pytest.approx(0.0, abs=1e-9)


def test_eval_points_denser_first_epoch():
    from deepdfa_tpu.llm.joint import JointConfig, eval_points

    cfg = JointConfig()
    first = eval_points(100, 0, cfg)
    later = eval_points(100, 1, cfg)
    assert len(first) == 5 and len(later) == 2  # first_eval_steps=5, eval_steps=2


@pytest.fixture(scope="module")
def joint_setup(tmp_path_factory):
    """Tiny end-to-end joint setup shared by the slow tests."""
    import jax

    from deepdfa_tpu.llm.fusion import FusionModel
    from deepdfa_tpu.llm.joint import JointConfig, JointTrainer
    from deepdfa_tpu.llm.llama import LlamaModel, tiny_llama

    llm_cfg = tiny_llama(vocab_size=320)
    llm = LlamaModel(llm_cfg)
    rng = np.random.default_rng(0)
    n = 24
    # learnable labels: vulnerable functions call "memcpy"
    labels = rng.integers(0, 2, size=n)
    funcs = [
        ("void f(){ memcpy(dst, src, n); }" if y else "void f(){ int a = 1; }")
        for y in labels
    ]
    examples = encode_functions(
        funcs, labels.tolist(), HashTokenizer(vocab_size=320), 16, indices=range(n)
    )
    graphs = random_dataset(n, seed=1, input_dim=INPUT_DIM, mean_nodes=6)
    for i, g in enumerate(graphs):
        g.gid = i
    gnn_cfg = GGNNConfig(hidden_dim=8, n_steps=2, num_output_layers=2)
    fusion = FusionModel(
        gnn_cfg=gnn_cfg,
        input_dim=INPUT_DIM,
        llm_hidden_size=llm_cfg.hidden_size,
        dropout_rate=0.1,
    )
    llm_params = llm.init(jax.random.key(0), np.zeros((2, 16), np.int32))["params"]
    trainer = JointTrainer(
        llm=llm,
        llm_params=llm_params,
        fusion=fusion,
        cfg=JointConfig(
            epochs=5, train_batch_size=4, eval_batch_size=4, learning_rate=5e-3,
            gradient_accumulation_steps=2, dataset_style="bigvul", seed=0,
        ),
        join=GraphJoin.from_list(graphs, max_nodes=512, max_edges=1024),
        run_dir=tmp_path_factory.mktemp("joint"),
    )
    # train here (module-scoped, once) so every test below is independently
    # runnable under ``pytest -k`` — no state smuggled between tests
    state = trainer.train(examples, examples)
    return trainer, examples, state


@pytest.mark.slow
def test_joint_training_learns(joint_setup):
    trainer, examples, state = joint_setup
    assert state is not None
    losses = [h["train_loss"] for h in trainer.history if "train_loss" in h]
    assert len(losses) == 5
    assert losses[-1] < losses[0]  # memcpy-vs-not is learnable by the LLM path
    # eval cadence ran during training and produced report keys
    evals = [h for h in trainer.history if "eval_loss" in h]
    assert evals and "eval_f1_macro" in evals[0]


@pytest.mark.slow
def test_joint_test_report(joint_setup):
    trainer, examples, state = joint_setup
    out = trainer.test(state.params, examples)
    assert "test_f1_macro" in out and "test_loss" in out
    assert out["test_f1_macro"] > 0.6  # separable by construction


def test_joint_checkpoint_roundtrip(joint_setup):
    import jax

    trainer, examples, state = joint_setup
    restored = trainer.load(state.params, "epoch_4")
    jax.tree.map(np.testing.assert_array_equal, state.params, restored)
    # no_missing in full join
    assert trainer.num_missing == 0


@pytest.mark.slow
def test_joint_resume_on_fresh_trainer(joint_setup):
    """Passing a resumed state to a trainer that never built its steps must
    work (ADVICE r1: _build was skipped when state was supplied)."""
    import dataclasses

    from deepdfa_tpu.llm.joint import JointTrainer

    trainer, examples, state = joint_setup
    fresh = JointTrainer(
        llm=trainer.llm,
        llm_params=trainer.llm_params,
        fusion=trainer.fusion,
        cfg=dataclasses.replace(trainer.cfg, epochs=1),
        join=trainer.join,
        run_dir=None,
    )
    resumed = fresh.train(examples, examples, state=state)
    assert resumed is not None
    assert int(resumed.step) > int(state.step)


@pytest.mark.slow
def test_joint_no_flowgnn_mode():
    """--no_flowgnn presets: LLM-only head, no graphs anywhere."""
    import jax

    from deepdfa_tpu.llm.fusion import FusionModel
    from deepdfa_tpu.llm.joint import JointConfig, JointTrainer
    from deepdfa_tpu.llm.llama import LlamaModel, tiny_llama

    llm_cfg = tiny_llama(vocab_size=320)
    llm = LlamaModel(llm_cfg)
    examples = _examples(n=8, block=12)
    fusion = FusionModel(
        gnn_cfg=GGNNConfig(hidden_dim=8, n_steps=1, num_output_layers=2),
        input_dim=INPUT_DIM,
        llm_hidden_size=llm_cfg.hidden_size,
        use_gnn=False,
    )
    llm_params = llm.init(jax.random.key(0), np.zeros((2, 12), np.int32))["params"]
    trainer = JointTrainer(
        llm=llm,
        llm_params=llm_params,
        fusion=fusion,
        cfg=JointConfig(epochs=1, dataset_style="devign"),
        join=None,
    )
    state = trainer.train(examples, examples)
    out = trainer.test(state.params, examples)
    assert "test_f1_weighted" in out  # weighted avg for balanced datasets


def test_presets_cover_reference_launch_scripts():
    """One preset per MSIVD launch script (scripts/*.sh), golden values."""
    from deepdfa_tpu.llm.presets import PRESETS

    # 5 MSIVD launch scripts + the 2 LineVul configs of BASELINE config #3
    # + the six frozen decoders (three routed-expert, one state-space, one power-retention, one
    # CCA with top-1 routed experts), each with its test-size twin
    assert set(PRESETS) == {
        "bigvul_ft_bigvul", "pretrained_bigvul", "pb_ft_pb",
        "pb_ft_pb_noexpl", "pretrained_pb", "linevul", "linevul_fusion",
        "longcat_flash_msivd", "tiny_longcat_msivd",
        "openpangu_ultra_msivd", "tiny_pangu_moe_msivd",
        "jamba2_3b_msivd", "tiny_jamba_msivd",
        "smallthinker_21b_msivd", "tiny_smallthinker_msivd",
        "brumby_14b_msivd", "tiny_brumby_msivd",
        "zaya1_8b_msivd", "tiny_zaya_msivd",
    }
    p = PRESETS["bigvul_ft_bigvul"]
    assert p.llm.hidden_size == 4096 and p.joint.block_size == 256
    assert p.joint.learning_rate == 1e-4 and p.joint.epochs == 5
    long = PRESETS["pb_ft_pb"]
    assert long.llm.hidden_size == 5120 and long.joint.block_size == 2048
    assert long.llm.attn_impl == "ring" and long.llm.lora_rank > 0
    assert long.mesh.sp == -1  # long blocks shard the sequence axis
    for name in ("pb_ft_pb_noexpl", "pretrained_pb"):
        assert PRESETS[name].joint.use_gnn is False  # --no_flowgnn parity


@pytest.mark.slow
def test_fusion_dense_layout_parity():
    """FusionModel with a dense-layout encoder matches the segment-layout
    encoder on SHARED parameters (one tree, two forwards), and GraphJoin
    emits the matching dense batches."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from deepdfa_tpu.llm.dataset import GraphJoin, HashTokenizer, encode_functions, text_batches
    from deepdfa_tpu.llm.fusion import FusionModel

    graphs = random_dataset(6, seed=1, input_dim=INPUT_DIM, mean_nodes=8)
    funcs = [f"int f{i}(int x) {{ return x + {i}; }}" for i in range(6)]
    ex = encode_functions(funcs, [i % 2 for i in range(6)],
                          HashTokenizer(vocab_size=64), 16, indices=range(6))
    tb = next(text_batches(ex, 6))

    cfg = GGNNConfig(hidden_dim=8, n_steps=2, num_output_layers=2)
    h = jnp.zeros((6, 16, 32), jnp.float32)
    tmask = jnp.asarray(tb.pad_mask)

    def build(layout):
        join = GraphJoin.from_list(graphs, max_nodes=512, max_edges=1024,
                                   layout=layout)
        batch = join.join(tb)
        model = FusionModel(
            gnn_cfg=dataclasses.replace(cfg, layout=layout),
            input_dim=INPUT_DIM, llm_hidden_size=32,
        )
        return model, batch

    m_seg, b_seg = build("segment")
    params = m_seg.init(jax.random.key(0), h, b_seg.graphs,
                        deterministic=True, token_mask=tmask)["params"]
    out_seg = np.asarray(m_seg.apply({"params": params}, h, b_seg.graphs,
                                     deterministic=True, token_mask=tmask))
    m_den, b_den = build("dense")
    out_den = np.asarray(m_den.apply({"params": params}, h, b_den.graphs,
                                     deterministic=True, token_mask=tmask))
    np.testing.assert_allclose(out_den, out_seg, rtol=1e-4, atol=1e-4)


def test_fusion_dense_missing_graph_embeds_zero():
    """A missing graph's placeholder (0 nodes) must produce a zero embedding
    in the dense layout too (masked softmax over an empty row)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from deepdfa_tpu.llm.dataset import GraphJoin, TextBatch
    from deepdfa_tpu.llm.fusion import FusionModel

    graphs = random_dataset(2, seed=2, input_dim=INPUT_DIM, mean_nodes=6)
    join = GraphJoin.from_list(graphs, layout="dense")
    tb = TextBatch(
        input_ids=np.zeros((3, 8), np.int32),
        labels=np.zeros(3, np.int32),
        indices=np.array([0, 999, 1]),  # 999 missing
        mask=np.ones(3, bool),
        pad_mask=np.ones((3, 8), bool),
    )
    jb = join.join(tb)
    assert join.num_missing == 1 and not jb.mask[1]
    cfg = GGNNConfig(hidden_dim=8, n_steps=2, num_output_layers=2,
                     layout="dense", encoder_mode=True, label_style="graph")
    from deepdfa_tpu.models import make_model

    enc = make_model(cfg, INPUT_DIM)
    db = jax.tree.map(jnp.asarray, jb.graphs)
    params = enc.init(jax.random.key(1), db)["params"]
    emb = np.asarray(enc.apply({"params": params}, db))
    assert np.allclose(emb[1], 0.0), emb[1]
    assert np.abs(emb[0]).max() > 0


def test_fusion_dense_oversize_graph_becomes_placeholder():
    """A graph over the dense per-graph budget is treated like a missing one
    (placeholder + mask=False, slot alignment preserved) instead of blowing
    every batch's adjacency up to the outlier's size."""
    import dataclasses as dc

    from deepdfa_tpu.llm.dataset import GraphJoin, TextBatch

    graphs = random_dataset(40, seed=3, input_dim=INPUT_DIM, mean_nodes=8)
    # one outlier far beyond p99 of the store
    big = random_dataset(1, seed=4, input_dim=INPUT_DIM, mean_nodes=200)[0]
    graphs.append(dc.replace(big, gid=777))
    join = GraphJoin.from_list(graphs, layout="dense")
    tb = TextBatch(
        input_ids=np.zeros((2, 8), np.int32),
        labels=np.zeros(2, np.int32),
        indices=np.array([0, 777]),
        mask=np.ones(2, bool),
        pad_mask=np.ones((2, 8), bool),
    )
    jb = join.join(tb)
    assert big.n_nodes > jb.graphs.nodes_per_graph  # budget excludes outlier
    assert join.num_oversize == 1
    assert jb.mask[0] and not jb.mask[1]


def test_graph_join_layout_whitelist():
    import pytest

    graphs = random_dataset(2, seed=5, input_dim=INPUT_DIM, mean_nodes=6)
    with pytest.raises(ValueError, match="unknown layout"):
        GraphJoin.from_list(graphs, layout="Dense")


def test_fusion_layout_mismatch_raises_nameable_error():
    """r03 advisor: GraphJoin(layout=dense) fed to FusionModel(layout=segment)
    used to surface as an opaque jit shape error — now a TypeError naming
    both layouts, raised before tracing."""
    import jax
    import jax.numpy as jnp
    import pytest

    from deepdfa_tpu.llm.dataset import GraphJoin, TextBatch
    from deepdfa_tpu.llm.fusion import FusionModel

    graphs = random_dataset(3, seed=6, input_dim=INPUT_DIM, mean_nodes=6)
    join = GraphJoin.from_list(graphs, layout="dense")
    tb = TextBatch(
        input_ids=np.zeros((2, 8), np.int32),
        labels=np.zeros(2, np.int32),
        indices=np.array([0, 1]),
        mask=np.ones(2, bool),
        pad_mask=np.ones((2, 8), bool),
    )
    jb = join.join(tb)
    cfg = GGNNConfig(hidden_dim=8, n_steps=2, num_output_layers=2,
                     layout="segment", encoder_mode=True, label_style="graph")
    fusion = FusionModel(gnn_cfg=cfg, input_dim=INPUT_DIM, llm_hidden_size=16)
    hidden = jnp.zeros((2, 8, 16), jnp.float32)
    with pytest.raises(TypeError, match="dense.*layout|layout.*dense"):
        fusion.init(jax.random.key(0), hidden, jb.graphs)
