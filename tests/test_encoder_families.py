"""``llm/families.py``: one record a family, and ``build_encoder`` over it —
what ``train_joint.py``, ``JointEngine.from_run_dir`` and ``presets.py`` read.
Hermetic, CPU, tiny sizes."""

import dataclasses
import json
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepdfa_tpu.llm.families import FAMILIES, build_encoder
from deepdfa_tpu.llm.joint import JointConfig

BLOCK = 16
NAMES = sorted(FAMILIES)
REPO = Path(__file__).resolve().parent.parent
BENCH_CONFIGS = REPO / "benchmark" / "configs"
if str(REPO / "scripts") not in sys.path:
    sys.path.insert(0, str(REPO / "scripts"))


def _spec(tree):
    """Structure, shapes and dtypes of a param tree (boxes included)."""
    return jax.tree.map(lambda x: (x.shape, jnp.dtype(x.dtype).name), tree)


def test_the_table_is_the_seven_families():
    assert NAMES == ["brumby", "jamba", "llama", "longcat", "pangu_moe", "roberta", "smallthinker",
                     "zaya"]
    for name, fam in FAMILIES.items():
        assert fam.name == name and fam.pool in ("cls", "last")
    # the families without a converter are the ones built from a seed
    assert [n for n in NAMES if FAMILIES[n].from_checkpoint is None] == [
        "brumby", "jamba", "longcat", "pangu_moe", "smallthinker", "zaya"]


@pytest.mark.parametrize("name", NAMES)
def test_build_encoder_at_the_hermetic_config(name):
    fam = FAMILIES[name]
    config_cls, model_cls = fam.classes()
    cfg = fam.hermetic(BLOCK)
    assert isinstance(cfg, config_cls) and cfg.vocab_size == 2048
    llm, params, tokenizer, got_cfg = build_encoder(fam, cfg, BLOCK)
    assert got_cfg is cfg and isinstance(llm, model_cls)
    assert tokenizer.vocab_size == cfg.vocab_size
    ids = np.random.default_rng(0).integers(3, cfg.vocab_size, (3, BLOCK))
    pad_mask = np.ones((3, BLOCK), bool)
    pad_mask[0, :5] = False
    hidden = llm.apply({"params": params}, jnp.asarray(ids), jnp.asarray(pad_mask))
    assert hidden.shape == (3, BLOCK, cfg.hidden_size)
    assert np.isfinite(np.asarray(hidden, np.float32)).all()
    # llm_cfg=None from a seed is the same hermetic pairing
    _, again, _, none_cfg = build_encoder(fam, None, BLOCK)
    assert none_cfg == cfg and _spec(again) == _spec(params)


@pytest.mark.parametrize("name", NAMES)
def test_family_agrees_with_its_presets_and_the_benchmark_configs(name):
    from deepdfa_tpu.llm.presets import PRESETS

    fam = FAMILIES[name]
    config_cls, _ = fam.classes()
    mine = [p for p in PRESETS.values() if p.encoder_family == name]
    assert mine, f"no preset of family {name}"
    for p in mine:
        assert isinstance(p.llm, config_cls), p.name
        assert p.joint.train_llm == fam.trained, p.name
    # where the head pools: what the cells of that family state
    cells = {"roberta": ["linevul", "linevul-fusion"], "longcat": ["longcat-flash-msivd"],
             "pangu_moe": ["openpangu-ultra-msivd"], "jamba": ["jamba2-3b-msivd"], "smallthinker": ["smallthinker-21b-msivd"],
             "brumby": ["brumby-14b-msivd"], "zaya": ["zaya1-8b-msivd"], "llama": []}[name]
    for cell in cells:
        cfg = json.loads((BENCH_CONFIGS / f"{cell}.json").read_text())
        assert cfg["head"]["pool"] == fam.pool, cell
    assert fam.pool == ("cls" if name == "roberta" else "last")


@pytest.mark.parametrize("name", NAMES)
def test_a_preset_of_another_familys_config_is_refused(name):
    from deepdfa_tpu.llm.presets import PRESETS, JointPreset

    other = FAMILIES[NAMES[(NAMES.index(name) + 1) % len(NAMES)]]
    base = next(p for p in PRESETS.values() if p.encoder_family == name)
    with pytest.raises(TypeError, match=f"encoder_family='{name}'"):
        dataclasses.replace(base, name="crossed", llm=other.hermetic(BLOCK))
    with pytest.raises(TypeError, match="crossed"):
        JointPreset(
            name="crossed", llm=other.hermetic(BLOCK), joint=base.joint,
            finetuned=False, mesh=base.mesh, dataset="bigvul", encoder_family=name)
    # its own config passes, whatever its size
    assert dataclasses.replace(base, llm=FAMILIES[name].hermetic(BLOCK)).llm.vocab_size == 2048


def test_joint_engine_restores_into_the_pairing_build_encoder_makes(tmp_path):
    """``from_run_dir``'s default encoder is ``build_encoder``'s hermetic
    llama: same tree (structure, shapes, dtypes), same weights, same
    tokenizer — by construction now, where a docstring used to promise it."""
    import orbax.checkpoint as ocp

    from deepdfa_tpu.llm.fusion import FusionModel
    from deepdfa_tpu.llm.joint_engine import JointEngine

    fam = FAMILIES["llama"]
    jcfg = JointConfig(block_size=BLOCK)
    llm, params, tokenizer, cfg = build_encoder(fam, fam.hermetic(BLOCK), BLOCK)
    fusion = FusionModel(
        gnn_cfg=None, input_dim=52, llm_hidden_size=cfg.hidden_size,
        use_gnn=False, dropout_rate=0.1, pool=fam.pool)
    saved = JointEngine._template_params(llm, params, fusion, jcfg, 64, 128)
    # one checkpointer: the save is asynchronous, and a second instance's
    # ``wait_until_finished`` waits for nothing (a loaded machine then restores
    # before ``epoch_0`` is renamed into place)
    with ocp.StandardCheckpointer() as checkpointer:
        checkpointer.save((tmp_path / "epoch_0").absolute(), saved)
        checkpointer.wait_until_finished()

    eng = JointEngine.from_run_dir(tmp_path, jcfg=jcfg, use_gnn=False)
    assert type(eng.llm) is type(llm) and eng.llm.cfg == cfg
    assert eng.fusion.pool == fam.pool
    assert _spec(eng.llm_params) == _spec(params)
    jax.tree.map(np.testing.assert_array_equal, eng.llm_params, params)
    assert eng.tokenizer.vocab_size == tokenizer.vocab_size == 2048
    jax.tree.map(np.testing.assert_array_equal, eng.fusion_params, saved)
    # and the vocabulary argument still reaches the hermetic config
    small = JointEngine.from_run_dir(tmp_path, jcfg=jcfg, use_gnn=False, vocab_size=320)
    assert small.llm.cfg.vocab_size == small.tokenizer.vocab_size == 320


@pytest.mark.parametrize(
    "name", [n for n in NAMES if FAMILIES[n].from_checkpoint is None])
def test_a_family_without_a_converter_refuses_a_checkpoint(name, tmp_path):
    with pytest.raises(ValueError, match=f"the {name} family has no checkpoint "
                       "conversion yet: it is built from a seed"):
        build_encoder(FAMILIES[name], FAMILIES[name].hermetic(BLOCK), BLOCK,
                      hf_checkpoint=str(tmp_path))


# -- weights from a local HF directory ----------------------------------------


class _DirTokenizer:
    """Stands in for ``transformers.AutoTokenizer`` (its import alone takes
    seconds): records the directory it was asked to load."""

    def __init__(self, path):
        self.path = path

    @classmethod
    def from_pretrained(cls, path):
        return cls(path)


@pytest.fixture
def llama_checkpoint(tmp_path, monkeypatch):
    """A tiny llama as an HF directory: ``config.json`` + safetensors under
    HF's names, written from a flax tree (the converter's inverse)."""
    from safetensors.numpy import save_file

    import flax.linen as nn

    from deepdfa_tpu.llm.llama import LlamaModel, tiny_llama

    monkeypatch.setitem(
        sys.modules, "transformers", types.SimpleNamespace(AutoTokenizer=_DirTokenizer))
    cfg = tiny_llama(vocab_size=96)
    tree = nn.meta.unbox(LlamaModel(cfg).init(
        jax.random.key(7), np.zeros((1, 4), np.int32))["params"])
    state = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        *parts, kind = [k.key.replace("layers_", "layers.") for k in path]
        arr = np.asarray(leaf, np.float32)
        state[".".join(["model", *parts, "weight"])] = np.ascontiguousarray(
            arr.T if kind == "kernel" else arr)
    save_file(state, str(tmp_path / "model.safetensors"))
    hf_keys = {f.name for f in dataclasses.fields(cfg)} - {
        "dtype", "attn_impl", "remat", "lora_rank", "lora_alpha", "int8_runtime"}
    (tmp_path / "config.json").write_text(
        json.dumps({k: getattr(cfg, k) for k in hf_keys}))
    return tmp_path, cfg, tree


def test_llama_from_a_checkpoint_keeps_the_presets_knobs(llama_checkpoint):
    ckpt, cfg, tree = llama_checkpoint
    knobs = dataclasses.replace(FAMILIES["llama"].hermetic(BLOCK), lora_alpha=8.0)
    llm, params, tokenizer, got = build_encoder(FAMILIES["llama"], knobs, BLOCK, hf_checkpoint=str(ckpt))
    # shapes from config.json, TPU-side knobs from the config handed in
    assert got == dataclasses.replace(cfg, lora_alpha=8.0) and got.dtype == "float32"
    assert llm.cfg == got and tokenizer.path == str(ckpt)
    jax.tree.map(np.testing.assert_array_equal, params, tree)
    # None: the checkpoint's own config, nothing laid over it
    _, _, _, own = build_encoder(FAMILIES["llama"], None, BLOCK, hf_checkpoint=str(ckpt))
    assert own == dataclasses.replace(cfg, dtype="bfloat16")


def test_llama_from_a_checkpoint_is_placed_over_the_mesh(llama_checkpoint):
    from deepdfa_tpu.parallel.mesh import local_mesh

    ckpt, cfg, tree = llama_checkpoint
    mesh = local_mesh(8, dp=2, tp=4)
    llm, params, _, got = build_encoder(
        FAMILIES["llama"], cfg, BLOCK, hf_checkpoint=str(ckpt), mesh=mesh)
    assert llm.mesh is mesh and got == cfg
    q = params["layers_0"]["self_attn"]["q_proj"]["kernel"]
    assert q.sharding.spec == jax.sharding.PartitionSpec("fsdp", "tp")
    assert q.addressable_shards[0].data.shape == (cfg.hidden_size, cfg.hidden_size // 4)
    jax.tree.map(np.testing.assert_array_equal, params, tree)
    ids = jnp.asarray(np.random.default_rng(1).integers(3, cfg.vocab_size, (2, BLOCK)))
    from deepdfa_tpu.llm.llama import LlamaModel

    want = LlamaModel(cfg).apply({"params": tree}, ids)
    out = jax.jit(lambda p, i: llm.apply({"params": p}, i))(params, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)


# -- the rule make_joint_steps keeps: trained <=> dropout keys ------------------


@pytest.mark.parametrize("train_llm", [False, True])
def test_only_a_trained_encoder_is_handed_dropout_keys(train_llm):
    """``train_llm`` alone decides (it is ``family.trained``): the step names
    no family and sniffs no config."""
    import optax

    from deepdfa_tpu.llm.dataset import HashTokenizer, encode_functions, text_batches
    from deepdfa_tpu.llm.fusion import FusionModel
    from deepdfa_tpu.llm.joint import JointState, JoinedBatch, make_joint_steps

    seen = []

    class Encoder:  # no ``cfg``: nothing to sniff
        def apply(self, variables, ids, pad_mask, mutable, **kw):
            seen.append(kw)
            return variables["params"]["table"][ids], {}

    ex = encode_functions(["int f(){}"] * 4, [0, 1, 0, 1], HashTokenizer(vocab_size=64),
                          BLOCK, indices=range(4))
    tb = next(text_batches(ex, 4))
    jb = JoinedBatch(text=tb, graphs=None, mask=tb.mask)
    llm_params = {"table": jnp.ones((64, 8))}
    fusion = FusionModel(gnn_cfg=None, input_dim=52, llm_hidden_size=8, use_gnn=False,
                         dropout_rate=0.1, pool="last")
    fparams = fusion.init(
        {"params": jax.random.key(0), "dropout": jax.random.key(1)},
        jnp.ones((4, BLOCK, 8)), None, deterministic=True,
        token_mask=jnp.asarray(tb.pad_mask))["params"]
    params = {"fusion": fparams, "llm": llm_params} if train_llm else fparams
    tx = optax.sgd(1e-2)
    train_step, eval_step = make_joint_steps(Encoder(), fusion, tx, train_llm=train_llm)
    state = JointState(params, tx.init(params), jax.random.key(2), jnp.zeros((), jnp.int32))
    state, loss, probs = train_step(state, None if train_llm else llm_params, jb)
    assert np.isfinite(float(loss)) and probs.shape == (4, 2)
    eval_step(state.params, None if train_llm else llm_params, jb)
    train_kw, eval_kw = seen
    assert eval_kw == {}  # evaluation is deterministic for every family
    if train_llm:
        assert train_kw["deterministic"] is False and set(train_kw["rngs"]) == {"dropout"}
    else:
        assert train_kw == {}


def test_train_joint_offers_the_tables_families():
    import train_joint

    with pytest.raises(SystemExit):
        train_joint.main(["--encoder", "bert"])
    with pytest.raises(SystemExit, match="contradicts preset 'linevul'"):
        train_joint.main(["--preset", "linevul", "--encoder", "longcat"])
