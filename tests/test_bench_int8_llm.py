"""Contract test for the full-model int8-resident inference bench: one
self-validating JSON line, int8 params randomised without an f32
materialisation, finiteness asserted."""

import json
import os
import subprocess
import sys
from pathlib import Path
import pytest

REPO = Path(__file__).resolve().parent.parent


@pytest.mark.slow
def test_tiny_emits_valid_json_line():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "bench_int8_llm.py"),
         "--tiny", "--chain", "2"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    d = json.loads(out.stdout.strip().splitlines()[-1])
    assert d["metric"] == "int8_resident_tokens_per_sec_per_chip"
    assert d["value"] is None or d["value"] > 0
    assert d["refused"] is None or isinstance(d["refused"], str)
    assert d["model"] == "tiny_llama" and d["full_model_measured"] is False
    # tiny depth reported, not the 7B default
    assert d["layers"] < 32


def test_randomize_params_respects_dtypes():
    # Shared randomizer (deepdfa_tpu.llm.quant): dtypes preserved, int8
    # nonzero, scales ~1e-2, norm weights KEPT at init, None passthrough.
    import jax.numpy as jnp

    from deepdfa_tpu.llm.quant import randomize_int8_runtime_params

    tree = {
        "q": jnp.zeros((4, 8), jnp.int8),
        "scale": jnp.ones((8,), jnp.float32),
        "embedding": jnp.zeros((16, 4), jnp.bfloat16),
        "input_layernorm": {"weight": jnp.ones((4,), jnp.float32)},
        "lora_a": None,
    }
    out = randomize_int8_runtime_params(tree, seed=0)
    assert out["q"].dtype == jnp.int8 and int(jnp.abs(out["q"]).max()) > 0
    assert out["scale"].dtype == jnp.float32
    assert float(jnp.abs(out["scale"]).max()) < 1.0  # ~1e-2 magnitudes
    assert out["embedding"].dtype == jnp.bfloat16
    assert float(jnp.abs(out["embedding"]).max()) > 0
    # RMSNorm weights keep their ones-init (randomising them suppresses
    # every residual branch ~50x)
    assert bool(jnp.all(out["input_layernorm"]["weight"] == 1.0))
    assert out["lora_a"] is None


@pytest.mark.slow
def test_tiny_decode_emits_valid_json_line():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "bench_int8_llm.py"),
         "--tiny", "--decode", "8", "--decode-prompt", "4"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    d = json.loads(out.stdout.strip().splitlines()[-1])
    assert d["metric"] == "int8_resident_decode_tokens_per_sec_per_chip"
    assert d["value"] > 0 and d["new_tokens"] == 8
    assert d["step_ms"] > 0
