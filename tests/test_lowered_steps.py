"""The joint train step of every family that a benchmark cell runs, lowered
and pinned by digest: CodeBERT trained, the six frozen decoders, each at a
tiny size whose widths its kernel takes, with the kernel rule as the CPU
gives it (the plain forms) and forced to the Pallas interpreter. A change
that means to move where the model code and its kernels meet, and nothing
else, keeps every one of these programs byte for byte."""

import hashlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from deepdfa_tpu.llm import roberta
from deepdfa_tpu.ops import dispatch

# sha256 of ``jit(train_step).lower(...).as_text()`` (jax 0.9.0), made by
# :func:`_lowered_step`. A PR that means to change one of these programs
# replaces its digest; one that does not must not.
STEPS = {
    ("roberta", "cpu"): "411ed386e9c708f2222b25728223c950660aaaf8c613a296f8a5c6d1174d256a",
    ("roberta", "interpret"): "cf8708ee55f9c342a6d47ee4b47bb992ddf43c3cee05d114568d7f6d10164b6e",
    ("longcat", "cpu"): "3b19427723cfd1e0ae37692953e8fd9348882f9cdb9392ea7c7099bacf7961b7",
    ("longcat", "interpret"): "8ec61373ad3ada7cd1fd9a66f523e4f274b4382be18d93f922702a08e00a5711",
    ("pangu_moe", "cpu"): "e52fc33787687b5c33a1cc8387864e585f5c64b6241a08b3f5acac411a0ced85",
    ("pangu_moe", "interpret"): "955a168beba0e1d47573d5e355dead51d3fc24d15b17ec3a224b322c700856dd",
    ("jamba", "cpu"): "d11a713fcbdaf50ae52237c0ca48a63f3a68dcd9ba13cd279592ae5383b55f97",
    ("jamba", "interpret"): "9dbdab9f3bd87a6140452575bf75ba49207a14b5deded9405e0156833272e4ca",
    ("smallthinker", "cpu"): "a2abdcd109eb91693864ef1686a8b316a7f5cd82763cd6b933fed66eb3987cfb",
    ("smallthinker", "interpret"): "6b8d21348c0bdd65dd46404fa98bb44c8e8e59c596c96db884a2489a5b73961b",
    ("brumby", "cpu"): "63697d414b7fa79c3b77349be8759ba40f97db1df7facc38fa44c89258774e7b",
    ("brumby", "interpret"): "8d3afaf8b36036c6de4c64c8a320dbc3f984f4a9b95097914784e64d8f68fb23",
    ("zaya", "cpu"): "753d11c43620f3d5021a737848b885262fbca9c9f5ab082495af922be96cb5a9",
    ("zaya", "interpret"): "6577148e565ffbddbb6496e5e944f80053ca96b935f1419968e778a5f0bb293a",
}


def _family(name: str):
    """``(model, block)`` at the smallest widths the family's kernel takes."""
    if name == "roberta":
        cfg = roberta.tiny_roberta(
            vocab_size=256, hidden_size=256, num_attention_heads=4, intermediate_size=512,
            attention_probs_dropout_prob=0.0, hidden_dropout_prob=0.0)
        return roberta.RobertaEncoder(cfg), 128
    if name == "longcat":
        from deepdfa_tpu.llm.longcat import LongcatModel, tiny_longcat

        return LongcatModel(tiny_longcat(
            vocab_size=256, experts_held=(2, 4), num_attention_heads=2, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128)), 128
    if name == "pangu_moe":
        from deepdfa_tpu.llm.pangu_moe import PanguMoeModel, tiny_pangu_moe

        return PanguMoeModel(tiny_pangu_moe(
            vocab_size=256, num_attention_heads=2, qk_nope_head_dim=128, qk_rope_head_dim=64,
            v_head_dim=128)), 128
    if name == "jamba":
        from deepdfa_tpu.llm.jamba import JambaModel, tiny_jamba

        return JambaModel(tiny_jamba(
            vocab_size=256, hidden_size=512, num_hidden_layers=3, mamba_d_state=16,
            mamba_dt_rank=16)), 48
    if name == "smallthinker":
        from deepdfa_tpu.llm.smallthinker import SmallThinkerModel, tiny_smallthinker

        return SmallThinkerModel(tiny_smallthinker(
            vocab_size=256, num_hidden_layers=4, head_dim=128, sliding_window_size=96)), 128
    if name == "brumby":
        from deepdfa_tpu.llm.brumby import BrumbyModel, tiny_brumby

        return BrumbyModel(tiny_brumby(
            vocab_size=256, hidden_size=256, num_attention_heads=2, num_key_value_heads=1,
            head_dim=128, retention_chunk=32)), 64
    if name == "zaya":
        from deepdfa_tpu.llm.zaya import ZayaModel, tiny_zaya

        return ZayaModel(tiny_zaya(
            vocab_size=256, hidden_size=256, num_hidden_layers=2, head_dim=128,
            layer_types=("hybrid",) * 2)), 128
    raise ValueError(name)


def _lowered_step(name: str) -> str:
    """The joint step's lowered text: the GGNN joined, the encoder trained
    for CodeBERT (as the LineVul cells run it) and frozen for a decoder."""
    from deepdfa_tpu.config import GGNNConfig
    from deepdfa_tpu.data.synthetic import random_dataset
    from deepdfa_tpu.llm.dataset import GraphJoin, HashTokenizer, encode_functions, text_batches
    from deepdfa_tpu.llm.fusion import FusionModel
    from deepdfa_tpu.llm.joint import JointConfig, JointTrainer

    llm, block = _family(name)
    trained = name == "roberta"
    jcfg = JointConfig(block_size=block, train_batch_size=2, eval_batch_size=2, epochs=1,
                       train_llm=trained, use_gnn=True)
    graphs = random_dataset(4, seed=0, input_dim=8)
    funcs = [f"int f{i}(int a) {{ return a + {i}; }}" * (1 + 4 * i) for i in range(4)]
    examples = encode_functions(
        funcs, [i % 2 for i in range(4)], HashTokenizer(vocab_size=llm.cfg.vocab_size),
        block, indices=[g.gid for g in graphs])
    hidden = llm.cfg.hidden_size
    fusion = FusionModel(gnn_cfg=GGNNConfig(hidden_dim=8, n_steps=2), input_dim=8,
                         llm_hidden_size=hidden, use_gnn=True, pool="cls" if trained else "last")
    ids = jnp.zeros((2, block), jnp.int32)
    params = nn.meta.unbox(llm.init(jax.random.key(0), ids, jnp.ones(ids.shape, bool))["params"])
    join = GraphJoin.from_list(graphs, max_nodes=512, max_edges=1024)
    trainer = JointTrainer(llm=llm, llm_params=params, fusion=fusion, cfg=jcfg, join=join)
    batch = trainer._joined(next(text_batches(examples, jcfg.train_batch_size)))
    state = trainer._build(3, batch)
    launch = trainer._steps[0]
    jitted = launch.__closure__[launch.__code__.co_freevars.index("jitted_train_step")]
    return jitted.cell_contents.lower(state, None if trained else params, batch).as_text()


@pytest.mark.parametrize("name,mode", sorted(STEPS))
def test_the_step_is_lowered_as_before(name, mode, monkeypatch):
    if mode == "interpret":
        monkeypatch.setattr(dispatch, "device_mode", lambda: True)
    text = _lowered_step(name)
    assert hashlib.sha256(text.encode()).hexdigest() == STEPS[name, mode]
