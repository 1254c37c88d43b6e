"""bench.py's self-validation contract: the refusal gate, the chained-batch
tiling, and the no-fallback rules (a backend the caller did not give, a
device kind without a peak and a stage that raises all end the run
non-zero). These are what make the emitted numbers trustworthy — a bench
that can't refuse impossible results is a bench that can lie (round-1
shipped a 3.7×-over-ceiling artifact exactly that way)."""

import json

import numpy as np
import pytest

import bench


def test_validate_refuses_over_roofline():
    refused = {}
    # 1000 g/s × 1e9 flops/graph = 1 TFLOP/s implied vs 0.5 TFLOP/s roofline
    out = bench._validate("value", 1000.0, 1e9, 1.0, 0.5e12, refused)
    assert out is None
    assert "value" in refused and "roofline" in refused["value"]


def test_validate_passes_under_roofline():
    refused = {}
    out = bench._validate("value", 1000.0, 1e9, 1.0, 2e12, refused)
    assert out == 1000.0 and not refused


def test_validate_without_flops_passes_through():
    """No cost analysis ⇒ nothing to check against — the number passes but
    the artifact carries flops_per_step=null for the reader."""
    refused = {}
    assert bench._validate("value", 123.4, None, 1.0, 1e12, refused) == 123.4
    assert not refused


def test_stack_tiled_cycles_distinct_batches():
    batches = [
        {"x": np.full((2, 3), i, np.float32)} for i in range(3)
    ]
    stacked = bench._stack_tiled(batches, k=7)
    vals = np.asarray(stacked["x"])[:, 0, 0]
    assert vals.tolist() == [0, 1, 2, 0, 1, 2, 0]


def test_layout_segment_skips_dense_stage():
    """--layout segment must record the skip verbatim so the artifact says
    why the dense column is null."""
    res = bench._assemble_result(
        "tpu", "TPU v5 lite", 169.5e12, {"nodes": 0.8, "edges": 0.8},
        243.0,
        {"graphs_per_sec": 76580.0, "flops_per_step": 1e9, "k": 128,
         "step_ms": 3.2, "wall_s": 0.4},
        dense_error="skipped (--layout segment)",
    )
    assert res["layout"] == "segment"
    assert res["dense_graphs_per_sec"] is None
    assert res["dense_error"] == "skipped (--layout segment)"
    assert res["segment_graphs_per_sec"] == 76580.0
    assert res["strict_graphs_per_sec"] is None  # not measured, not faked


def test_peak_batches_usage_error_exits_2():
    """A malformed --peak-batches must be a usage error (rc=2), not a
    crash mid-run."""
    with pytest.raises(SystemExit) as ei:
        bench._build_parser().parse_args(["--peak-batches", "1024x2048"])
    assert ei.value.code == 2
    # and the default parses through the same type callable
    ns = bench._build_parser().parse_args([])
    assert ns.peak_batches == (1024,)  # 2048 is opt-in
    assert bench._build_parser().parse_args(
        ["--peak-batches", ""]).peak_batches == ()


def test_assemble_fused_schema_and_winner():
    """The fused stage's columns land in the artifact and the headline goes
    to the fastest validated layout; the loser's RAW number survives in
    layout_compare instead of being discarded."""
    res = bench._assemble_result(
        "tpu", "TPU v5 lite", 169.5e12, {"nodes": 0.8, "edges": 0.8},
        243.0,
        {"graphs_per_sec": 76580.0, "flops_per_step": 19.3e9, "k": 128,
         "step_ms": 3.2, "wall_s": 0.4},
        fused={"graphs_per_sec": 120000.0, "flops_per_step": 9.6e9,
               "k": 128, "step_ms": 1.0, "wall_s": 0.2},
        fused_real=121.5, fused_batch_graphs=128,
        dense_error="skipped (--layout fused)",
    )
    assert res["layout"] == "fused" and res["value"] == 120000.0
    assert res["fused_graphs_per_sec"] == 120000.0
    assert res["fused_step_ms"] == 1.0
    assert res["fused_flops_per_step"] == 9.6e9
    assert res["fused_graphs_per_batch"] == 121.5
    assert res["fused_batch_graphs"] == 128
    assert res["fused_error"] is None
    assert res["dense_error"] == "skipped (--layout fused)"
    lc = res["layout_compare"]
    assert lc["winner"] == "fused"
    assert lc["fused"] == {"graphs_per_sec_raw": 120000.0,
                           "graphs_per_sec": 120000.0}
    # the losing segment rate is recorded, not discarded (round-5 gap)
    assert lc["segment"] == {"graphs_per_sec_raw": 76580.0,
                             "graphs_per_sec": 76580.0}


def test_assemble_fused_refusal_keeps_raw_in_layout_compare():
    """A fused rate past the roofline is refused from the headline and the
    fused column, but the raw measurement stays in layout_compare."""
    res = bench._assemble_result(
        "tpu", "TPU v5 lite", 169.5e12, {"nodes": 0.8, "edges": 0.8},
        243.0,
        {"graphs_per_sec": 76580.0, "flops_per_step": 19.3e9, "k": 128,
         "step_ms": 3.2, "wall_s": 0.4},
        fused={"graphs_per_sec": 1e9, "flops_per_step": 57.9e9,
               "k": 128, "step_ms": 0.01, "wall_s": 0.2},
        fused_real=128.0, fused_batch_graphs=128,
    )
    assert res["layout"] == "segment" and res["value"] == 76580.0
    assert res["fused_graphs_per_sec"] is None
    assert "fused_graphs_per_sec" in res["refused"]
    assert res["layout_compare"]["fused"]["graphs_per_sec_raw"] == 1e9
    assert res["layout_compare"]["fused"]["graphs_per_sec"] is None
    assert res["layout_compare"]["winner"] == "segment"


# ---------------------------------------------------------------------------
# sentinel-overhead guard (resilience invariant: guard < 2% of a step)


@pytest.mark.faults
def test_sentinel_overhead_pct_math():
    assert bench.sentinel_overhead_pct(1.0, 1.015) == pytest.approx(1.5)
    assert bench.sentinel_overhead_pct(2.0, 2.0) == 0.0
    # guard measured FASTER than plain = timing noise, reported negative
    assert bench.sentinel_overhead_pct(1.0, 0.99) == pytest.approx(-1.0)
    with pytest.raises(ValueError):
        bench.sentinel_overhead_pct(0.0, 1.0)


@pytest.mark.faults
def test_sentinel_guard_budget():
    assert bench.sentinel_guard_ok(1.99)
    assert bench.sentinel_guard_ok(-3.0)
    assert not bench.sentinel_guard_ok(2.01)
    assert bench.sentinel_guard_ok(4.9, budget=5.0)


# ---------------------------------------------------------------------------
# no fallback: wrong backend, unknown device, failed stage ⇒ non-zero exit


def test_unknown_device_kind_is_an_error():
    """The peaks table is keyed by the device_kind the chip reports; a kind
    without a row raises instead of nulling every nominal-MFU column."""
    assert bench._nominal_peak_tflops("TPU v5 lite") == 197.0
    with pytest.raises(ValueError, match="no datasheet peak"):
        bench._nominal_peak_tflops("SomethingElse")
    with pytest.raises(ValueError, match="'cpu'"):
        bench._assemble_result(
            "cpu", "cpu", 1e12, {"nodes": 0.8, "edges": 0.8}, 243.0,
            {"graphs_per_sec": 10.0, "flops_per_step": 1e9, "k": 8,
             "step_ms": 3.2, "wall_s": 0.4})


def test_require_backend_refuses_what_it_was_not_given():
    """No platform given ⇒ the TPU or nothing (JAX itself drops to CPU with
    only a warning); an explicit pin gets exactly that platform, labelled."""
    import jax

    from deepdfa_tpu import utils

    assert jax.config.jax_platforms == "cpu"  # conftest pins it
    where = utils.require_backend()
    assert where["backend"] == "cpu" and where["device_count"] >= 1
    jax.config.update("jax_platforms", "")
    try:
        with pytest.raises(utils.BackendError, match="default is 'tpu'"):
            utils.require_backend()
        jax.config.update("jax_platforms", "tpu")
        with pytest.raises(utils.BackendError, match="asked for 'tpu'"):
            utils.require_backend()
    finally:
        jax.config.update("jax_platforms", "cpu")


def test_bench_stage_that_raises_fails_the_run(monkeypatch, capsys):
    """A stage failure propagates out of main (⇒ non-zero exit): no JSON
    line with an "error" field under a green exit code."""
    monkeypatch.setattr(bench, "_progress", lambda *_: None)
    monkeypatch.setattr(bench, "start_on_device",
                        lambda: ("tpu", "TPU v5 lite"))
    monkeypatch.setattr(bench, "build_corpus", lambda n, d: [])
    monkeypatch.setattr(
        bench, "build_batches",
        lambda corpus, n, batch_graphs=256: (
            [type("B", (), {"graph_mask": np.ones(4, bool)})()],
            {"nodes": 1.0, "edges": 1.0, "graphs": 1.0}))
    monkeypatch.setattr(bench, "measure_roofline", lambda: 1e14)

    def chained(batches, k, train, **kw):
        if train:
            raise RuntimeError("train stage blew up")
        return {"graphs_per_sec": 10.0, "flops_per_step": 1e9, "k": k,
                "step_ms": 1.0, "wall_s": 0.1}

    monkeypatch.setattr(bench, "bench_chained", chained)
    with pytest.raises(RuntimeError, match="train stage blew up"):
        bench.main(["--layout", "segment", "--skip-baseline",
                    "--peak-batches", ""])
    assert capsys.readouterr().out == ""


def test_bench_refuses_device_without_a_peak(monkeypatch, capsys):
    monkeypatch.setattr(bench, "_progress", lambda *_: None)
    monkeypatch.setattr(bench, "start_on_device", lambda: ("cpu", "cpu"))
    with pytest.raises(ValueError, match="no datasheet peak"):
        bench.main(["--layout", "segment", "--skip-baseline"])
    assert capsys.readouterr().out == ""


def test_cost_flops_does_not_swallow_a_failing_compile():
    """A lower/compile failure would fail the timed run too: it propagates
    instead of becoming flops_per_step=null under a green exit code."""
    class _Broken:
        def lower(self, *args):
            raise RuntimeError("Mosaic failed to compile TPU kernel")

    with pytest.raises(RuntimeError, match="Mosaic failed"):
        bench._cost_flops(_Broken())

    class _NoFlops:
        def lower(self, *args):
            return self

        def compile(self):
            return self

        def cost_analysis(self):
            return [{"bytes accessed": 1.0}]

    assert bench._cost_flops(_NoFlops()) is None
