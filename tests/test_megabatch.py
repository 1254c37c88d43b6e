"""Megabatch packing + whole-model fusion (ops/megabatch.py,
models/ggnn_megabatch.py, the engine's ``score_packed``): the PR-11
acceptance gates that run device-free.

Pinned here:

- the byte-exact VMEM plan classifies EVERY packer-emitted shape across a
  corpus sweep (the ``working_set_bytes`` discipline of the fused-layout
  guard, extended to the whole-model kernel's extra blocks);
- packing efficiency on the realworld fixture corpus meets the ≥0.95
  graphs-axis target, and megabatch dispatches/step are STRICTLY lower
  than the per-bucket ladder on the same corpus;
- packed multi-bucket batches agree with the segment layout: kernel path
  ≤1e-5 forward / ≤1e-4 grad on shared params, and the over-plan
  fallback (``megabatch_reference``) is BITWISE segment math;
- routing: over-plan shapes pin to the segment twin (model-level and
  Trainer-level), never the kernel;
- serving: ``score_packed`` dispatches once where the ladder walks
  several, preserves input order, routes over-budget graphs through the
  ladder, and the padding-efficiency gauges flow through ServeMetrics to
  ``/metrics`` exposition.
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepdfa_tpu.config import ALL_SUBKEYS, ExperimentConfig, FeatureConfig, GGNNConfig
from deepdfa_tpu.data.graphs import GraphBatcher, derive_buckets
from deepdfa_tpu.data.synthetic import random_dataset
from deepdfa_tpu.models import make_model
from deepdfa_tpu.models.ggnn import GGNN
from deepdfa_tpu.models.ggnn_megabatch import GGNNMegabatch
from deepdfa_tpu.ops import megabatch as mb

INPUT_DIM = 52
SMALL = dict(hidden_dim=8, n_steps=3, num_output_layers=2)
N_SUB = len(ALL_SUBKEYS)
# the SMALL config's kernel dims, as GGNNMegabatch.plan_for derives them
DIMS = dict(width=SMALL["hidden_dim"] * N_SUB, n_steps=SMALL["n_steps"],
            table_rows=INPUT_DIM * N_SUB, embed_width=SMALL["hidden_dim"],
            n_head_layers=SMALL["num_output_layers"])


def _pack(graphs, **kw):
    return mb.pack_megabatches(graphs, **{**DIMS, **kw})


def _models(cfg_kwargs=SMALL):
    cfg = GGNNConfig(**cfg_kwargs)
    seg = GGNN(cfg=cfg, input_dim=INPUT_DIM)
    mega = GGNNMegabatch(cfg=dataclasses.replace(cfg, layout="megabatch"),
                         input_dim=INPUT_DIM)
    return seg, mega


def _mixed_corpus(seed=0, n_small=10, n_mid=4):
    """Graphs from two size classes — a packed megabatch spans buckets."""
    return (random_dataset(n_small, seed=seed, input_dim=INPUT_DIM,
                           mean_nodes=6)
            + random_dataset(n_mid, seed=seed + 1, input_dim=INPUT_DIM,
                             mean_nodes=25))


def _realworld_graphs():
    from deepdfa_tpu.cpg.frontend import parse_source
    from deepdfa_tpu.data.materialize import CorpusBuilder

    fixtures = Path(__file__).parent / "fixtures" / "realworld"
    names = sorted(json.loads((fixtures / "goldens.json").read_text()))
    cpgs = {i: parse_source((fixtures / f"{n}.c").read_text())
            for i, n in enumerate(names)}
    builder = CorpusBuilder(FeatureConfig(limit_subkeys=50, limit_all=50))
    graphs, _ = builder.build(cpgs, train_ids=list(cpgs),
                              vuln_lines={i: set() for i in cpgs})
    assert graphs, "no fixture graphs materialised"
    return graphs


# ------------------------------------------------------------ VMEM plan


def test_plan_bytes_monotone_and_count_padding():
    kw = dict(table_rows=208, embed_width=8, n_head_layers=2)
    base = mb.megabatch_working_set_bytes(100, 200, 32, 10, **kw)
    assert base <= mb.megabatch_working_set_bytes(101, 200, 32, 10, **kw)
    assert base <= mb.megabatch_working_set_bytes(100, 201, 32, 10, **kw)
    assert base <= mb.megabatch_working_set_bytes(100, 200, 33, 10, **kw)
    assert base <= mb.megabatch_working_set_bytes(100, 200, 32, 11, **kw)
    # and the whole-model plan strictly dominates the message-passing plan
    from deepdfa_tpu.ops.fused_ggnn import working_set_bytes

    assert base > working_set_bytes(100, 200, 32)
    # padding rules: nodes→8, width/graphs→128 lanes
    assert mb.megabatch_working_set_bytes(
        1, 1, 1, 1, **kw) == mb.megabatch_working_set_bytes(8, 1, 128, 128, **kw)


@pytest.mark.parametrize("mean_nodes,seed", [(8, 0), (30, 1), (70, 2)])
def test_every_packer_emitted_shape_is_classified_exactly(mean_nodes, seed):
    """The sweep gate: for every bin the packer emits across corpus
    regimes, the byte-exact plan must (a) admit it, (b) agree with
    ``fits_vmem_megabatch``, and (c) match the batch's actual padded
    shape — no shape can reach the kernel without its plan."""
    graphs = random_dataset(120, seed=seed, input_dim=INPUT_DIM,
                            mean_nodes=mean_nodes)
    pack = _pack(graphs)
    assert pack.batches, "packer emitted nothing"
    assert not pack.oversize  # corpus-scale graphs always fit singly
    n_packed = 0
    for batch, plan in zip(pack.batches, pack.plans):
        assert plan.fits and plan.working_set <= mb.VMEM_CAP_BYTES
        assert mb.fits_vmem_megabatch(
            plan.max_nodes, plan.max_edges, plan.width, plan.max_graphs,
            table_rows=plan.table_rows, embed_width=plan.embed_width,
            n_head_layers=plan.n_head_layers)
        # batch shape IS the plan shape
        assert batch.node_mask.shape[0] == plan.max_nodes
        assert batch.senders.shape[0] == plan.max_edges
        assert batch.graph_mask.shape[0] == plan.max_graphs
        # batch_np contract: one padding sink node + one sink graph slot
        real_g = int(np.sum(batch.graph_mask))
        assert real_g == plan.max_graphs - 1
        assert int(np.sum(batch.node_mask)) <= plan.max_nodes - 1
        n_packed += real_g
    assert n_packed == len(graphs)  # every graph accounted, exactly once


def test_packer_efficiency_realworld_fixtures_meets_floor():
    """The acceptance pin: ≥0.95 graphs-axis packing efficiency on the
    realworld fixture corpus at serving load (the fixture set replicated
    to a request-window's worth of graphs)."""
    graphs = _realworld_graphs() * 4
    pack = _pack(graphs)
    assert not pack.oversize
    assert pack.efficiency["graphs"] >= 0.95, pack.efficiency
    # node-axis efficiency only loses the rounding slack + sink node
    assert pack.efficiency["nodes"] > 0.5, pack.efficiency


def test_packer_uniform_mode_one_compiled_shape():
    graphs = _mixed_corpus(seed=3, n_small=16, n_mid=5)
    pack = _pack(graphs, max_batch_graphs=12, uniform=True)
    assert len(pack.batches) >= 2
    shapes = {(b.graph_mask.shape[0], b.node_mask.shape[0],
               b.senders.shape[0]) for b in pack.batches}
    assert len(shapes) == 1  # ONE compiled shape for the scan chain
    assert len(set(map(id, pack.plans))) == 1  # the shared union plan
    total = sum(int(np.sum(b.graph_mask)) for b in pack.batches)
    assert total == len(graphs)


def test_packer_uniform_mode_balances_bins():
    """Uniform mode snake-deals graphs across bins instead of re-padding
    greedy FFD bins to their fullest member: bin populations differ by at
    most one graph, so the shared union shape stays tight and the last
    bin is not mostly padding (a 127+127+2 split priced at 128 slots per
    bin is the failure mode this pins against)."""
    graphs = _mixed_corpus(seed=7, n_small=40, n_mid=12)
    pack = _pack(graphs, max_batch_graphs=16, uniform=True)
    assert len(pack.batches) >= 3
    counts = [int(np.sum(b.graph_mask)) for b in pack.batches]
    assert max(counts) - min(counts) <= 1, counts
    assert sum(counts) == len(graphs)
    # the union's graphs axis carries exactly the fullest bin + the sink
    assert pack.plans[0].max_graphs == max(counts) + 1
    # balanced dealing keeps the graphs axis near-full everywhere: the
    # only overhead is the per-bin sink slot and the <=1-graph imbalance
    floor = min(counts) / (max(counts) + 1)
    assert pack.efficiency["graphs"] >= floor


def test_packer_routes_oversize_to_ladder(monkeypatch):
    """A graph whose SINGLE-graph plan is refused must come back in
    ``oversize`` (the caller's ladder/segment-twin route), never in a
    batch — exercised by shrinking the cap, the same lever the routing
    tests use."""
    graphs = random_dataset(12, seed=4, input_dim=INPUT_DIM, mean_nodes=10)
    monkeypatch.setattr(mb, "VMEM_CAP_BYTES", 0)
    pack = _pack(graphs)
    assert not pack.batches and not pack.plans
    assert len(pack.oversize) == len(graphs)
    assert pack.efficiency == {"nodes": 0.0, "edges": 0.0, "graphs": 0.0}


def test_dispatches_per_step_strictly_lower_than_ladder():
    """The tentpole's arithmetic: megabatch dispatches (packed bins +
    oversize) must be STRICTLY below the per-bucket ladder's batch count
    on the same corpus."""
    graphs = _mixed_corpus(seed=5, n_small=60, n_mid=20)
    ladder = len(list(GraphBatcher(
        derive_buckets(graphs, 32)).batches(graphs)))
    pack = _pack(graphs)
    mega_dispatches = len(pack.batches) + len(pack.oversize)
    assert mega_dispatches < ladder, (mega_dispatches, ladder)


# ------------------------------------------------------ model-level parity


def _packed_batch(graphs):
    pack = _pack(graphs)
    assert len(pack.batches) == 1 and not pack.oversize
    return jax.tree.map(jnp.asarray, pack.batches[0])


def test_param_trees_identical_and_fresh_init_bit_identical():
    seg, mega = _models()
    batch = _packed_batch(_mixed_corpus())
    ps = seg.init(jax.random.key(0), batch)["params"]
    pm = mega.init(jax.random.key(0), batch)["params"]
    flat_s = {jax.tree_util.keystr(p): v
              for p, v in jax.tree_util.tree_leaves_with_path(ps)}
    flat_m = {jax.tree_util.keystr(p): v
              for p, v in jax.tree_util.tree_leaves_with_path(pm)}
    assert set(flat_s) == set(flat_m)
    for k in flat_s:
        np.testing.assert_array_equal(np.asarray(flat_s[k]),
                                      np.asarray(flat_m[k]), err_msg=k)


def test_kernel_matches_segment_forward_on_packed_multibucket_batch():
    """The whole-model kernel (interpret mode — same code the TPU
    compiles) vs the segment forward on SHARED params, over a packed
    batch spanning two size classes."""
    batch = _packed_batch(_mixed_corpus(seed=6))
    seg, mega = _models()
    params = seg.init(jax.random.key(0), batch)["params"]
    assert mega.plan_for(batch.node_mask.shape[0], batch.senders.shape[0],
                         batch.graph_mask.shape[0]).fits  # kernel path
    out_s = np.asarray(seg.apply({"params": params}, batch))
    out_m = np.asarray(mega.apply({"params": params}, batch))
    np.testing.assert_allclose(out_m, out_s, rtol=1e-5, atol=1e-5)


def test_overplan_fallback_is_bitwise_segment(monkeypatch):
    """With the cap forced to zero every shape is over-plan: the model
    must route to ``megabatch_reference`` and match the segment layout
    BIT FOR BIT (same ops, same order, same params)."""
    batch = _packed_batch(_mixed_corpus(seed=7))
    seg, mega = _models()
    params = seg.init(jax.random.key(0), batch)["params"]
    monkeypatch.setattr(mb, "VMEM_CAP_BYTES", 0)
    out_s = np.asarray(seg.apply({"params": params}, batch))
    out_m = np.asarray(mega.apply({"params": params}, batch))
    np.testing.assert_array_equal(out_m, out_s)


def test_gradient_parity_through_custom_vjp_on_packed_batch():
    batch = _packed_batch(_mixed_corpus(seed=8, n_small=6, n_mid=2))
    seg, mega = _models()
    params = seg.init(jax.random.key(0), batch)["params"]

    def loss(model, p):
        return jnp.sum(model.apply({"params": p}, batch) ** 2)

    gs = jax.grad(lambda p: loss(seg, p))(params)
    gm = jax.grad(lambda p: loss(mega, p))(params)
    gm_map = {jax.tree_util.keystr(p): v
              for p, v in jax.tree_util.tree_leaves_with_path(gm)}
    for p, v in jax.tree_util.tree_leaves_with_path(gs):
        k = jax.tree_util.keystr(p)
        np.testing.assert_allclose(np.asarray(gm_map[k]), np.asarray(v),
                                   rtol=1e-4, atol=1e-5, err_msg=k)


def test_make_model_dispatches_megabatch_and_rejects_variants():
    cfg = GGNNConfig(**SMALL, layout="megabatch")
    assert isinstance(make_model(cfg, input_dim=INPUT_DIM), GGNNMegabatch)
    batch = _packed_batch(_mixed_corpus(seed=9, n_small=4, n_mid=0))
    for bad, match in [
        (dataclasses.replace(cfg, aggregation="union_relu"), "sum"),
        (dataclasses.replace(cfg, label_style="node"), "graph-level"),
        (dataclasses.replace(cfg, dataflow_families=True), "concat-subkey"),
        (dataclasses.replace(cfg, interproc_families=True), "concat-subkey"),
    ]:
        with pytest.raises(ValueError, match=match):
            GGNNMegabatch(cfg=bad, input_dim=INPUT_DIM).init(
                jax.random.key(0), batch)
    # taps are a segment-layout diagnostic
    model = GGNNMegabatch(cfg=cfg, input_dim=INPUT_DIM)
    params = model.init(jax.random.key(0), batch)
    with pytest.raises(ValueError, match="taps"):
        model.apply(params, batch, taps=())


# ------------------------------------------------------- trainer routing


def _trainer():
    from deepdfa_tpu.train.loop import Trainer

    cfg = ExperimentConfig()
    cfg = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, layout="megabatch",
                                       **SMALL))
    model = make_model(cfg.model, input_dim=INPUT_DIM)
    return Trainer(model=model, cfg=cfg), cfg


def test_trainer_routes_fitting_megabatch_to_primary():
    tr, _cfg = _trainer()
    batch = _packed_batch(_mixed_corpus(seed=10, n_small=4, n_mid=0))
    ts, es = tr.steps_for(batch)
    assert ts is tr.train_step and es is tr.eval_step
    state = tr.init_state(batch)
    state, metrics, loss = tr.train_epoch(state, [batch])
    assert np.isfinite(loss)


def test_trainer_routes_overplan_megabatch_to_segment_twin(monkeypatch):
    tr, _cfg = _trainer()
    batch = _packed_batch(_mixed_corpus(seed=11, n_small=4, n_mid=0))
    monkeypatch.setattr(mb, "VMEM_CAP_BYTES", 0)
    ts, es = tr.steps_for(batch)
    assert ts is tr.fallback_train_step and es is tr.fallback_eval_step


# ------------------------------------------------------------- serving


def _chain(n, keys=("_ABS_DATAFLOW",)):
    from deepdfa_tpu.data.graphs import Graph

    feats = {k: np.zeros(n, np.int32) for k in keys}
    return Graph(senders=np.arange(n - 1, dtype=np.int32),
                 receivers=np.arange(1, n, dtype=np.int32),
                 node_feats=feats).with_self_loops()


def _stub_engine(mega=True, max_batch=4):
    from deepdfa_tpu.serve import ScoringEngine, serve_buckets
    from deepdfa_tpu.serve.engine import mega_bucket

    calls = []

    def score_fn(batch):
        calls.append(int(np.sum(np.asarray(batch.graph_mask))))
        return np.arange(batch.max_graphs, dtype=np.float32) / 100.0

    eng = ScoringEngine(score_fn, serve_buckets(max_batch),
                        feat_keys=("_ABS_DATAFLOW",),
                        mega=mega_bucket(max_batch) if mega else None)
    eng.calls = calls
    return eng


def test_score_packed_one_dispatch_and_input_order():
    """A mixed window that the ladder would split across size classes goes
    down as ONE mega dispatch, results keyed to input order."""
    eng = _stub_engine()
    graphs = [_chain(n) for n in (8, 200, 5, 60, 12, 300, 7, 9)]
    before = eng.n_dispatches
    out = eng.score_packed(graphs)
    assert eng.n_dispatches - before == 1
    assert out.shape == (len(graphs),)
    # the stub scores by slot index; FFD places the largest graph first,
    # so input order being preserved means out is NOT simply arange
    eff = eng.last_padding_efficiency
    assert eff is not None and set(eff) == {"nodes", "edges", "graphs"}
    assert 0.0 < eff["graphs"] <= 1.0
    # ladder comparison on the same window: strictly more dispatches
    eng2 = _stub_engine()
    for g in graphs:
        eng2.score([g], eng2.assign_bucket(g))
    assert eng2.n_dispatches > 1


def test_score_packed_routes_over_budget_graphs_through_ladder():
    eng = _stub_engine()
    spec = eng.mega_bucket.spec
    big = _chain(spec.max_nodes + 10)  # over the mega node budget
    out = eng.score_packed([_chain(8), big, _chain(5)])
    assert out.shape == (3,)
    # the big graph dispatched alone through its ladder bucket
    assert 1 in eng.calls
    assert eng.n_dispatches == 2  # one mega bin + one ladder dispatch


def test_score_packed_requires_mega_bucket_and_handles_empty():
    eng = _stub_engine(mega=False)
    with pytest.raises(RuntimeError, match="megabatch"):
        eng.score_packed([_chain(4)])
    eng2 = _stub_engine()
    assert eng2.score_packed([]).shape == (0,)
    assert eng2.n_dispatches == 0


def test_serve_metrics_padding_efficiency_exposition():
    """observe_padding → snapshot → Prometheus render: cumulative real ÷
    padded per (bucket, axis), one gauge family."""
    from deepdfa_tpu.serve.metrics import ServeMetrics

    m = ServeMetrics()
    m.observe_padding(126, real={"nodes": 50, "edges": 100, "graphs": 3},
                      padded={"nodes": 128, "edges": 512, "graphs": 5})
    m.observe_padding(126, real={"nodes": 78, "edges": 156, "graphs": 4},
                      padded={"nodes": 128, "edges": 512, "graphs": 5})
    eff = m.padding_efficiency()
    assert eff["126"]["nodes"] == pytest.approx(128 / 256)
    assert eff["126"]["graphs"] == pytest.approx(7 / 10)
    assert m.snapshot()["padding_efficiency"] == eff
    text = m.render()
    assert "# TYPE deepdfa_serve_padding_efficiency gauge" in text
    assert ('deepdfa_serve_padding_efficiency'
            '{bucket="126",axis="nodes"} 0.5') in text


def test_batcher_feeds_padding_gauges():
    """The micro-batcher records every dispatched batch's padding into the
    metrics sink (what the serve `/metrics` endpoint exposes)."""
    from deepdfa_tpu.serve.batcher import MicroBatcher
    from deepdfa_tpu.serve.metrics import ServeMetrics

    eng = _stub_engine(mega=False)
    metrics = ServeMetrics()
    batcher = MicroBatcher(eng, max_batch=4, max_wait_ms=1.0,
                           metrics=metrics).start()
    futs = [batcher.submit(_chain(8)) for _ in range(3)]
    for f in futs:
        f.result(timeout=30)
    batcher.stop(drain=True, timeout=30)
    eff = metrics.padding_efficiency()
    assert eff, "no padding observations recorded"
    (bucket,) = {k for k in eff}
    assert 0.0 < eff[bucket]["graphs"] <= 1.0
    assert 0.0 < eff[bucket]["nodes"] <= 1.0


def test_plan_counts_smem_ids_and_edges():
    """ids (n_sub rows of the padded node count) and edge endpoints share
    the 1 MiB of SMEM: the plan refuses what the compiler would."""
    from deepdfa_tpu.ops.fused_ggnn import SMEM_CAP_BYTES, edge_smem_bytes

    assert mb.megabatch_smem_bytes(2176, 4352, 4) == (
        edge_smem_bytes(4352) + 4 * 2176 * 4)
    kw = dict(width=128, n_steps=5, table_rows=4008, embed_width=32,
              n_head_layers=3)
    ok = mb.MegabatchPlan(max_graphs=129, max_nodes=2176, max_edges=4352,
                          **kw)
    assert ok.fits
    edge_heavy = mb.MegabatchPlan(max_graphs=9, max_nodes=1024,
                                  max_edges=131072, **kw)
    assert edge_heavy.working_set <= mb.VMEM_CAP_BYTES
    assert mb.megabatch_smem_bytes(1024, 131072, 4) > SMEM_CAP_BYTES
    assert not edge_heavy.fits
    assert not mb.fits_vmem_megabatch(
        1024, 131072, 128, 9, table_rows=4008, embed_width=32,
        n_head_layers=3)


def test_whole_model_kernel_rejects_a_ragged_stacked_table():
    """The prologue gathers whole lane-placed rows; which lanes a row owns
    comes from its sub-table, so the stack must split evenly."""
    import jax.numpy as jnp

    f32, i32 = jnp.float32, jnp.int32
    d, ed, n, e, g = 8, 4, 8, 8, 2
    with pytest.raises(ValueError, match="not a multiple"):
        mb.fused_ggnn_encoder(
            jnp.zeros((7, ed), f32), jnp.zeros((n, 2), i32),
            jnp.zeros((e,), i32), jnp.zeros((e,), i32),
            jnp.zeros((n,), i32), jnp.ones((n,), bool),
            jnp.zeros((d, d), f32), jnp.zeros((d,), f32),
            jnp.zeros((d, 3 * d), f32), jnp.zeros((3 * d,), f32),
            jnp.zeros((d, 3 * d), f32), jnp.zeros((3 * d,), f32),
            jnp.zeros((2 * d, 1), f32), jnp.zeros((1,), f32),
            n_steps=1, n_graphs=g, interpret=True)


def test_model_says_when_it_computes_through_the_twin(monkeypatch, caplog):
    """GGNNMegabatch's own over-plan path is not silent: a direct
    model.apply on a refused shape logs the routing (once per trace)."""
    import logging

    import jax
    import jax.numpy as jnp

    from deepdfa_tpu.config import GGNNConfig
    from deepdfa_tpu.data.graphs import batch_np
    from deepdfa_tpu.data.synthetic import random_dataset
    from deepdfa_tpu.models import make_model

    cfg = GGNNConfig(hidden_dim=8, n_steps=2, num_output_layers=2,
                     layout="megabatch")
    model = make_model(cfg, input_dim=40)
    graphs = random_dataset(4, seed=0, input_dim=40, mean_nodes=8)
    batch = jax.tree.map(jnp.asarray, batch_np(graphs, 5, 64, 128))
    params = model.init(jax.random.key(0), batch)["params"]
    with caplog.at_level(logging.WARNING, logger="deepdfa_tpu"):
        admitted = model.apply({"params": params}, batch)
    assert "segment-twin math" not in caplog.text
    monkeypatch.setattr(mb, "VMEM_CAP_BYTES", 0)
    with caplog.at_level(logging.WARNING, logger="deepdfa_tpu"):
        routed = model.apply({"params": params}, batch)
    assert "over the whole-model kernel's plan" in caplog.text
    np.testing.assert_allclose(np.asarray(routed), np.asarray(admitted),
                               atol=1e-5)
