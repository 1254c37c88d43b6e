"""Fused VMEM-resident Pallas GGNN (ops/fused_ggnn.py + models/ggnn_fused.py):
numerical parity with the segment-layout forward on SHARED parameters, run
under the Pallas interpreter (``interpret=True`` — the same kernel code the
TPU compiles). The segment path is the semantics anchor (itself parity-tested
against the torch/DGL reference in ``test_ggnn_parity.py``), so agreement
here chains the fused kernel to the reference semantics. Also: gradient
parity through the ``custom_vjp``, parameter-tree interchange, the Trainer's
VMEM routing, and the static VMEM-budget guard that walks every bucket shape
the k-bucket DPs can emit (a config change must fail HERE, not on-chip)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepdfa_tpu.config import ExperimentConfig, FeatureConfig, GGNNConfig
from deepdfa_tpu.data.graphs import BucketSpec, GraphBatcher, derive_buckets
from deepdfa_tpu.data.synthetic import random_dataset
from deepdfa_tpu.models import make_model
from deepdfa_tpu.models.ggnn import GGNN
from deepdfa_tpu.models.ggnn_fused import GatedGraphConvFused, GGNNFused
from deepdfa_tpu.ops import fused_ggnn as fg

INPUT_DIM = 52
SMALL = dict(hidden_dim=8, n_steps=3, num_output_layers=2)


def _corpus(n=8, seed=0, mean_nodes=12):
    return random_dataset(n, seed=seed, input_dim=INPUT_DIM,
                          mean_nodes=mean_nodes)


def _batch(graphs, max_nodes=512, max_edges=1024):
    b = next(GraphBatcher(
        [BucketSpec(len(graphs) + 1, max_nodes, max_edges)]).batches(graphs))
    return jax.tree.map(jnp.asarray, b)


def _models(cfg_kwargs=SMALL):
    cfg = GGNNConfig(**cfg_kwargs)
    seg = GGNN(cfg=cfg, input_dim=INPUT_DIM)
    fus = GGNNFused(cfg=dataclasses.replace(cfg, layout="fused"),
                    input_dim=INPUT_DIM)
    return seg, fus


# ---------------------------------------------------------------- kernel


def _rand_problem(rng, n, d, e, scale=0.1):
    h0 = rng.standard_normal((n, d)).astype(np.float32)
    rcv = np.sort(rng.integers(0, n, e)).astype(np.int32)
    snd = rng.integers(0, n, e).astype(np.int32)
    ew = (rng.standard_normal((d, d)) * scale).astype(np.float32)
    eb = (rng.standard_normal((d,)) * scale).astype(np.float32)
    xw = (rng.standard_normal((d, 3 * d)) * scale).astype(np.float32)
    xb = (rng.standard_normal((3 * d,)) * scale).astype(np.float32)
    hw = (rng.standard_normal((d, 3 * d)) * scale).astype(np.float32)
    hb = (rng.standard_normal((3 * d,)) * scale).astype(np.float32)
    return h0, snd, rcv, ew, eb, xw, xb, hw, hb


@pytest.mark.parametrize("n,d,e", [
    (5, 8, 7),        # below every tile minimum
    (37, 96, 120),    # unaligned everything
    (64, 128, 256),   # exactly tile-aligned
    (130, 200, 1),    # single edge, width past one lane tile
])
def test_kernel_matches_unrolled_reference(n, d, e):
    rng = np.random.default_rng(n * 1000 + d + e)
    args = _rand_problem(rng, n, d, e)
    out = fg.fused_ggnn(*args, n_steps=4, interpret=True)
    ref = fg._unrolled_reference(*args, 4, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_kernel_n_steps_zero_is_identity():
    rng = np.random.default_rng(0)
    args = _rand_problem(rng, 12, 16, 20)
    out = fg.fused_ggnn(*args, n_steps=0, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), args[0])


def test_kernel_duplicate_edges_accumulate():
    # repeated (s, r) pairs must each contribute — the self-loop-padding
    # contract depends on repeated sink-node edges summing
    rng = np.random.default_rng(1)
    h0, _, _, ew, eb, xw, xb, hw, hb = _rand_problem(rng, 10, 16, 0)
    snd = np.array([3, 3, 3, 7], np.int32)
    rcv = np.array([2, 2, 2, 9], np.int32)
    out = fg.fused_ggnn(h0, snd, rcv, ew, eb, xw, xb, hw, hb,
                        n_steps=2, interpret=True)
    ref = fg._unrolled_reference(h0, snd, rcv, ew, eb, xw, xb, hw, hb, 2, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_kernel_gradients_match_reference():
    rng = np.random.default_rng(2)
    h0, snd, rcv, ew, eb, xw, xb, hw, hb = _rand_problem(rng, 24, 32, 60)

    def loss_fused(h0_, ew_, xw_, hb_):
        out = fg.fused_ggnn(h0_, snd, rcv, ew_, eb, xw_, xb, hw, hb_,
                            n_steps=3, interpret=True)
        return jnp.sum(out ** 2)

    def loss_ref(h0_, ew_, xw_, hb_):
        out = fg._unrolled_reference(h0_, snd, rcv, ew_, eb, xw_, xb, hw,
                                     hb_, 3, True)
        return jnp.sum(out ** 2)

    gf = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(h0, ew, xw, hb)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(h0, ew, xw, hb)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


# ------------------------------------------------- model-level parity


def test_param_trees_identical_and_fresh_init_bit_identical():
    seg, fus = _models()
    batch = _batch(_corpus())
    ps = seg.init(jax.random.key(0), batch)["params"]
    pf = fus.init(jax.random.key(0), batch)["params"]
    flat_s = {jax.tree_util.keystr(p): v
              for p, v in jax.tree_util.tree_leaves_with_path(ps)}
    flat_f = {jax.tree_util.keystr(p): v
              for p, v in jax.tree_util.tree_leaves_with_path(pf)}
    assert set(flat_s) == set(flat_f)
    for k in flat_s:
        assert flat_s[k].shape == flat_f[k].shape, k
        # identical scope paths + init fns ⇒ same RNG folds ⇒ same values
        np.testing.assert_array_equal(np.asarray(flat_s[k]),
                                      np.asarray(flat_f[k]))


def test_fused_matches_segment_forward_synthetic():
    graphs = _corpus()
    batch = _batch(graphs)
    seg, fus = _models()
    params = seg.init(jax.random.key(0), batch)["params"]
    out_s = np.asarray(seg.apply({"params": params}, batch))
    out_f = np.asarray(fus.apply({"params": params}, batch))
    np.testing.assert_allclose(out_f, out_s, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mean_nodes,n_graphs,seed", [
    (6, 12, 1),    # many tiny graphs
    (30, 6, 2),    # mid-size
    (70, 3, 3),    # few large graphs
])
def test_fused_matches_segment_over_bucket_shapes(mean_nodes, n_graphs, seed):
    """Property test over the bucket-shape space: corpus statistics drive
    the derived bucket (exactly the trainer's batching), shapes vary with
    the corpus, parity must hold at every one."""
    graphs = random_dataset(n_graphs, seed=seed, input_dim=INPUT_DIM,
                            mean_nodes=mean_nodes)
    buckets = derive_buckets(graphs, len(graphs))
    batch = next(GraphBatcher(buckets).batches(graphs))
    batch = jax.tree.map(jnp.asarray, batch)
    seg, fus = _models()
    params = seg.init(jax.random.key(seed), batch)["params"]
    out_s = np.asarray(seg.apply({"params": params}, batch))
    out_f = np.asarray(fus.apply({"params": params}, batch))
    np.testing.assert_allclose(out_f, out_s, rtol=1e-5, atol=1e-5)


def test_fused_matches_segment_on_realworld_fixtures():
    """Every graph in tests/fixtures/realworld/ through the REAL extraction
    pipeline (frontend → features → graph), fused vs segment ≤ 1e-5."""
    import json
    from pathlib import Path

    from deepdfa_tpu.cpg.frontend import parse_source
    from deepdfa_tpu.data.materialize import CorpusBuilder

    fixtures = Path(__file__).parent / "fixtures" / "realworld"
    names = sorted(json.loads((fixtures / "goldens.json").read_text()))
    cpgs = {i: parse_source((fixtures / f"{n}.c").read_text())
            for i, n in enumerate(names)}
    builder = CorpusBuilder(FeatureConfig(limit_subkeys=50, limit_all=50))
    graphs, _ = builder.build(
        cpgs, train_ids=list(cpgs),
        vuln_lines={i: set() for i in cpgs},
    )
    assert graphs, "no fixture graphs materialised"
    input_dim = FeatureConfig(limit_subkeys=50, limit_all=50).input_dim
    batch = next(GraphBatcher(
        [BucketSpec(len(graphs) + 1, 2048, 4096)]).batches(graphs))
    batch = jax.tree.map(jnp.asarray, batch)
    cfg = GGNNConfig(**SMALL)
    seg = GGNN(cfg=cfg, input_dim=input_dim)
    fus = GGNNFused(cfg=dataclasses.replace(cfg, layout="fused"),
                    input_dim=input_dim)
    params = seg.init(jax.random.key(0), batch)["params"]
    out_s = np.asarray(seg.apply({"params": params}, batch))
    out_f = np.asarray(fus.apply({"params": params}, batch))
    np.testing.assert_allclose(out_f, out_s, rtol=1e-5, atol=1e-5)


def test_model_gradient_parity_through_custom_vjp():
    graphs = _corpus(6, seed=4)
    batch = _batch(graphs)
    seg, fus = _models()
    params = seg.init(jax.random.key(0), batch)["params"]

    def loss(model, p):
        return jnp.sum(model.apply({"params": p}, batch) ** 2)

    gs = jax.grad(lambda p: loss(seg, p))(params)
    gf = jax.grad(lambda p: loss(fus, p))(params)
    flat_s = jax.tree_util.tree_leaves_with_path(gs)
    gf_map = {jax.tree_util.keystr(p): v
              for p, v in jax.tree_util.tree_leaves_with_path(gf)}
    for p, v in flat_s:
        k = jax.tree_util.keystr(p)
        np.testing.assert_allclose(np.asarray(gf_map[k]), np.asarray(v),
                                   rtol=1e-4, atol=1e-5, err_msg=k)


def test_make_model_dispatches_fused_and_rejects_unknown():
    cfg = GGNNConfig(**SMALL, layout="fused")
    assert isinstance(make_model(cfg, input_dim=INPUT_DIM), GGNNFused)
    with pytest.raises(ValueError, match="unknown layout"):
        make_model(dataclasses.replace(cfg, layout="nope"),
                   input_dim=INPUT_DIM)


def test_fused_conv_rejects_segment_only_features():
    with pytest.raises(ValueError, match="sum"):
        GGNNFused(cfg=GGNNConfig(**SMALL, aggregation="union_relu",
                                 layout="fused"),
                  input_dim=INPUT_DIM).init(
            jax.random.key(0), _batch(_corpus(4)))
    conv = GatedGraphConvFused(out_feats=8, n_steps=2)
    h = jnp.zeros((4, 8))
    snd = jnp.array([0, 1], jnp.int32)
    rcv = jnp.array([1, 2], jnp.int32)
    params = conv.init(jax.random.key(0), h, snd, rcv)
    with pytest.raises(ValueError, match="taps"):
        conv.apply(params, h, snd, rcv,
                   taps=(jnp.zeros((4, 8)),) * 2)
    with pytest.raises(ValueError, match="sorted"):
        conv.apply(params, h, snd, jnp.array([2, 0], jnp.int32))


# ------------------------------------------------- trainer routing


def _trainer(layout="fused"):
    from deepdfa_tpu.train.loop import Trainer

    cfg = ExperimentConfig()
    cfg = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, layout=layout, **SMALL))
    model = make_model(cfg.model, input_dim=INPUT_DIM)
    return Trainer(model=model, cfg=cfg), cfg


def test_trainer_fused_routes_fitting_batch_to_primary():
    tr, _cfg = _trainer()
    batch = _batch(_corpus(6, seed=7))
    ts, es = tr.steps_for(batch)
    assert ts is tr.train_step and es is tr.eval_step
    state = tr.init_state(batch)
    state, metrics, loss = tr.train_epoch(state, [batch])
    assert np.isfinite(loss)


def test_trainer_fused_routes_vmem_oversize_to_segment_twin():
    tr, cfg = _trainer()
    width = cfg.model.out_dim // 2

    class _Fake:
        node_gidx = np.zeros(1, np.int32)
        node_mask = np.zeros(400_000, bool)
        senders = np.zeros(800_000, np.int32)

    assert not fg.fits_vmem(400_000, 800_000, width)
    ts, es = tr.steps_for(_Fake())
    assert ts is tr.fallback_train_step and es is tr.fallback_eval_step


def test_trainer_counts_and_logs_twin_routed_steps(monkeypatch, caplog):
    """Routing to the segment twin is never silent: an admitted bucket
    counts 0 twin-routed steps, an over-plan bucket counts 1 and logs the
    first one."""
    import logging

    tr, _cfg = _trainer()
    batch = _batch(_corpus(6, seed=7))
    state = tr.init_state(batch)
    with caplog.at_level(logging.WARNING, logger="deepdfa_tpu"):
        state, _m, loss = tr.train_epoch(state, [batch])
    assert np.isfinite(loss) and tr.twin_routed_steps == 0
    assert "segment twin" not in caplog.text

    monkeypatch.setattr(fg, "VMEM_CAP_BYTES", 0)  # every bucket over-plan
    with caplog.at_level(logging.WARNING, logger="deepdfa_tpu"):
        state, _m, loss = tr.train_epoch(state, [batch, batch])
    assert np.isfinite(loss) and tr.twin_routed_steps == 2
    assert caplog.text.count("trains on the segment twin") == 1  # first only
    monkeypatch.undo()
    state, _m, _loss = tr.train_epoch(state, [batch])
    assert tr.twin_routed_steps == 0  # per-epoch count


# ------------------------------------------------- VMEM budget guard


def _guard_widths():
    # golden config width (hidden 32 × concat4 = 128) and the widened
    # dataflow-families config (hidden 32 × (4 + 3 families) = 224)
    return [GGNNConfig().out_dim // 2,
            GGNNConfig(dataflow_families=True).out_dim // 2]


def test_vmem_guard_every_dp_bucket_is_classified_exactly():
    """Walk every bucket shape the segment k-bucket DP can emit across a
    corpus sweep and both configured widths: ``fits_vmem`` must agree with
    the byte-exact ``working_set_bytes`` plan at every shape, so no shape
    can slip past the router into the kernel with an over-cap working set
    — the refusal is static, before any Mosaic compile."""
    import bench

    n_over = 0
    for mean_nodes, seed in [(12, 0), (50, 1), (90, 2)]:
        corpus = random_dataset(300, seed=seed, input_dim=INPUT_DIM,
                                mean_nodes=mean_nodes)
        for bg in (32, 64, bench.FUSED_BATCH_GRAPHS):
            for spec in derive_buckets(corpus, bg):
                for width in _guard_widths():
                    ws = fg.working_set_bytes(spec.max_nodes,
                                              spec.max_edges, width)
                    assert fg.fits_vmem(
                        spec.max_nodes, spec.max_edges, width
                    ) == (ws <= fg.VMEM_CAP_BYTES), spec
                    # the conservative cap leaves slack below the physical
                    # 128 MiB even for admitted shapes' transient overheads
                    if ws <= fg.VMEM_CAP_BYTES:
                        assert ws < fg.VMEM_BYTES
                    else:
                        n_over += 1
    # the sweep must actually exercise the refusal branch (mean-90 corpus
    # at bg=128 emits ~15k-node buckets past the cap)
    assert n_over > 0


def test_vmem_guard_golden_corpus_fits_at_every_dispatch_size():
    """The Big-Vul-shaped bench corpus (the golden config's distribution)
    must fit the plan at the golden width for every bucket the DP emits at
    bg ≤ FUSED_BATCH_GRAPHS — a future hidden-width or fused-batch bump
    that would OOM VMEM on-chip fails here first."""
    import bench

    golden_width = GGNNConfig().out_dim // 2
    corpus = bench.build_corpus(600, FeatureConfig().input_dim)
    for bg in (32, 64, bench.FUSED_BATCH_GRAPHS):
        for spec in derive_buckets(corpus, bg):
            ws = fg.working_set_bytes(spec.max_nodes, spec.max_edges,
                                      golden_width)
            assert ws <= fg.VMEM_CAP_BYTES, (
                f"bucket {spec} at width {golden_width} needs "
                f"{ws / 2**20:.1f} MiB > cap "
                f"{fg.VMEM_CAP_BYTES / 2**20:.0f} MiB")


def test_vmem_guard_dense_dp_sizes_fit_per_graph():
    """Every per-graph size the dense k-bucket DP (data/dense.py) can emit
    stays trivially inside the plan even for a full fused batch of
    worst-case graphs at the widest configured width."""
    import bench
    from deepdfa_tpu.data.dense import derive_dense_sizes

    for mean_nodes, seed in [(12, 3), (50, 4), (90, 5)]:
        corpus = random_dataset(300, seed=seed, input_dim=INPUT_DIM,
                                mean_nodes=mean_nodes)
        for width in _guard_widths():
            for size in derive_dense_sizes(corpus, k=6):
                # a batch of FUSED_BATCH_GRAPHS graphs all at this size,
                # edges bounded by the corpus worst case of ~3 per node
                n = size * bench.FUSED_BATCH_GRAPHS
                ws = fg.working_set_bytes(n, 3 * n, width)
                if not fg.fits_vmem(n, 3 * n, width):
                    # over-cap shapes are legal — but the router MUST
                    # refuse them (fallback twin), never the kernel
                    assert ws > fg.VMEM_CAP_BYTES
                    assert not fg.fits_vmem(n, 3 * n, width)


def test_vmem_guard_worst_case_configured_ceiling_falls_back():
    """The configured worst-case budgets (BatchConfig: 40960 nodes / 81920
    edges) exceed the plan at every width — documents that the Trainer's
    segment-twin fallback is load-bearing for the overflow bucket."""
    from deepdfa_tpu.config import BatchConfig

    b = BatchConfig()
    for width in _guard_widths():
        assert not fg.fits_vmem(b.max_nodes, b.max_edges, width)


def test_vmem_guard_fused_bench_bucket_fits():
    """The shapes the bench's fused stage actually dispatches must fit."""
    import bench

    corpus = bench.build_corpus(int(2 * 256 * 1.5 * 2),
                                FeatureConfig().input_dim)
    batches, _ = bench.build_batches(corpus, 2,
                                     batch_graphs=bench.FUSED_BATCH_GRAPHS)
    width = GGNNConfig().out_dim // 2
    for b in batches:
        assert fg.fits_vmem(b.max_nodes, b.senders.shape[0], width)


def test_working_set_is_monotone_and_counts_padding():
    assert (fg.working_set_bytes(100, 200, 128)
            <= fg.working_set_bytes(101, 200, 128))
    assert (fg.working_set_bytes(100, 200, 128)
            <= fg.working_set_bytes(100, 201, 128))
    assert (fg.working_set_bytes(100, 200, 128)
            <= fg.working_set_bytes(100, 200, 129))
    # padding rules: width pads to the 128-lane tile, nodes to sublane 8
    assert fg.working_set_bytes(1, 1, 1) == fg.working_set_bytes(8, 1, 128)


def test_plan_caps_the_edge_indices_at_smem():
    """The edge endpoints are scalar-prefetched into SMEM (1 MiB on the
    v5e): an edge-heavy bucket is refused by the plan even when its VMEM
    working set is small — the compiler would refuse it at 2 x 512 KiB."""
    assert fg.edge_smem_bytes(1) == 2 * 128 * 4
    assert fg.edge_smem_bytes(4352) == 2 * 4352 * 4
    n, width = 1024, 128
    assert fg.working_set_bytes(n, 131072, width) < fg.VMEM_CAP_BYTES
    assert fg.edge_smem_bytes(131072) > fg.SMEM_CAP_BYTES
    assert not fg.fits_vmem(n, 131072, width)
    assert not fg.fits_vmem_train(n, 131072, width, 5)
    assert fg.fits_vmem(n, 65536, width)  # 512 KiB of indices: admitted
    assert fg.SMEM_CAP_BYTES < 2**20
