"""The one rule that picks, for every model, its Pallas kernel (compiled or
interpreted) or the plain form (``ops/dispatch.py``): each model's rule
takes the shapes its kernel takes and refuses the others, where no kernel
can run it imports no Pallas module, and each kernel compiles for a
described v5e at the size its benchmark cell runs. This file's fixture is
the one place in the suite that loads the TPU compiler."""

import dataclasses
import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from deepdfa_tpu.llm import brumby, jamba, longcat, pangu_moe, roberta, smallthinker, zaya
from deepdfa_tpu.ops import dispatch
from deepdfa_tpu.ops import flash_attention as flash

_ST_WIDE = dataclasses.replace(smallthinker.tiny_smallthinker(), head_dim=128)

# a model's rule, a (config, length) its kernel takes and one it refuses
RULES = [
    pytest.param(lambda cfg, s: roberta._fused_attention(cfg, s, True),
                 (roberta.codebert_base(), 512), (roberta.codebert_base(), 96),
                 id="flash_attention-roberta"),
    pytest.param(longcat._fused_attention, (longcat.longcat_flash(), 2048),
                 (longcat.tiny_longcat(), 2048), id="latent_attention-longcat"),
    pytest.param(longcat._fused_attention, (pangu_moe.openpangu_ultra_moe(), 2048),
                 (pangu_moe.openpangu_ultra_moe(), 2000), id="latent_attention-pangu_moe"),
    pytest.param(smallthinker._fused_attention, (_ST_WIDE, 256),
                 (smallthinker.tiny_smallthinker(), 256), id="gqa_attention-heads_of_16"),
    pytest.param(smallthinker._fused_attention, (smallthinker.smallthinker_21b(), 8192),
                 (_ST_WIDE, 200), id="gqa_attention-rows_of_200"),
    pytest.param(jamba._fused_scan, (jamba.jamba2_3b(), 2048), (jamba.tiny_jamba(), 2048),
                 id="selective_scan-jamba"),
    pytest.param(brumby._fused_retention, (brumby.brumby_14b(), 8192),
                 (brumby.tiny_brumby(), 8192), id="power_retention-brumby"),
    # CCA's latent: 8 query heads over 2 key/value heads of 128 at the cell's 8,192 positions
    pytest.param(zaya._fused_attention, (zaya.zaya1_8b(), 8192), (zaya.tiny_zaya(), 8192),
                 id="gqa_attention-zaya"),
]


@pytest.mark.parametrize("rule,taken,refused", RULES)
def test_a_kernel_is_taken_where_it_can_run_and_its_shape_holds(rule, taken, refused, monkeypatch):
    assert rule(*taken) is None  # the CPU: no kernel, whatever the shape allows
    monkeypatch.setattr(dispatch, "device_mode", lambda: True)
    assert rule(*taken) is True and rule(*refused) is None
    monkeypatch.setattr(dispatch, "device_mode", lambda: False)  # one TPU device
    assert rule(*taken) is False and rule(*refused) is None


def test_off_the_tpu_no_kernel_module_is_imported():
    """Pallas costs a second of imports, paid only where a kernel can run:
    in a fresh process on the CPU, asking for every kernel at a shape it
    takes imports neither Pallas nor a kernel's module."""
    code = f"""
import json, sys
from deepdfa_tpu.ops import dispatch
modes = [dispatch.kernel_mode(m, *shape) for m, shape in {[
        ("flash_attention", (512, 12, 64)), ("latent_attention", (2048, 64, 128, 64, 128)),
        ("gqa_attention", (8192, 28, 4, 128)), ("selective_scan", (2048, 5120, 16)),
        ("power_retention", (8192, 40, 8, 128, 128))]!r}]
loaded = sorted(m for m in sys.modules if "pallas" in m or m.startswith("deepdfa_tpu.ops."))
print(json.dumps([modes, loaded]))
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=300,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr
    modes, loaded = json.loads(out.stdout.splitlines()[-1])
    assert modes == [None] * 5 and loaded == ["deepdfa_tpu.ops.dispatch"]


@pytest.fixture(scope="module")
def one_v5e():
    """A described (not attached) v5e chip to compile for; the TPU compiler
    is loaded by this fixture alone, in the worker that runs this file."""
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_kernels_compile_for_the_v5e_at_codeberts_size(one_v5e):
    """Mosaic takes both kernels at [16, 512, 12 x 64] float32 (what the
    interpreter cannot show: tiling, VMEM), and the compiled backward holds no
    temporary near a score tensor's 201 MB."""
    b, s, heads, d = 16, 512, 12, 64
    x = jax.ShapeDtypeStruct((b, s, heads * d), jnp.float32, sharding=one_v5e)
    seg = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one_v5e)
    grads = jax.grad(lambda q, k, v, seg: jnp.sum(
        flash.flash_attention(q, k, v, seg, num_heads=heads)), argnums=(0, 1, 2))
    compiled = jax.jit(grads).trace(x, x, x, seg).lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert "flash_attention_fwd" in text and "flash_attention_bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


def test_the_latent_attention_kernel_compiles_for_the_v5e_at_the_decoders_size(one_v5e):
    """Here because this file's fixture is the one place that loads the TPU
    compiler: ``ops/latent_attention`` at [4, 2048, 64 x (192 | 128)]
    bfloat16 (tiling, VMEM, the loop with bounds from SMEM), and nothing near
    a query block's 268 MB of float32 scores among the temporaries."""
    from deepdfa_tpu.ops.latent_attention import latent_attention

    b, s, h = 4, 2048, 64
    shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(dims, dtype, sharding=one_v5e)
    compiled = jax.jit(functools.partial(latent_attention, num_heads=h)).trace(
        shape(b, s, h * 128), shape(b, s, h * 64), shape(b, s, 64), shape(b, s, h * 256),
        shape(b, s, dtype=jnp.bool_),
    ).lower(lowering_platforms=("tpu",)).compile()
    assert "latent_attention_fwd" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**20


def test_the_selective_scan_kernel_compiles_for_the_v5e_at_the_decoders_size(one_v5e):
    """Here for the fixture's sake too: ``ops/selective_scan.gated_scan`` at
    [4, 2048, 5120] x 16 states, bfloat16, ``z`` as the second half of
    ``in_proj``'s output (the strided stores and loads of the relayout, the
    SMEM windows, VMEM), and no float32 array of the sequence's size
    (168 MB) among the temporaries."""
    from deepdfa_tpu.ops.selective_scan import gated_scan

    b, s, d, n = 4, 2048, 5120, 16
    shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(dims, dtype, sharding=one_v5e)
    f32 = functools.partial(shape, dtype=jnp.float32)
    compiled = jax.jit(functools.partial(gated_scan, interpret=False)).trace(
        shape(b, s, d), shape(b, s, d), f32(d), f32(d, n), shape(b, s, n), shape(b, s, n),
        f32(d), shape(b, s, 2 * d), shape(b, s, dtype=jnp.bool_),
    ).lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert "selective_scan_fwd" in text and " while(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**20


def test_the_grouped_query_attention_kernel_compiles_for_the_v5e_at_the_decoders_size(one_v5e):
    """Here for the fixture's sake too: ``ops/gqa_attention`` at
    [2, 8192, 28 | 4 x 128] bfloat16, global and with the 4096-token window
    (tiling, the resident row of keys and values in VMEM, the three loops with
    bounds from SMEM), and nothing near a query block's 0.94 GB of float32
    scores among the temporaries."""
    from deepdfa_tpu.ops.gqa_attention import gqa_attention

    b, s, h, hk = 2, 8192, 28, 4
    shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(dims, dtype, sharding=one_v5e)
    for window in (None, 4096):
        compiled = jax.jit(functools.partial(gqa_attention, num_kv_heads=hk, window=window)).trace(
            shape(b, s, h * 128), shape(b, s, hk * 128), shape(b, s, hk * 128),
            shape(b, s, dtype=jnp.bool_),
        ).lower(lowering_platforms=("tpu",)).compile()
        assert "gqa_attention_fwd" in compiled.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**20


def test_the_power_retention_kernel_compiles_for_the_v5e_at_the_decoders_size(one_v5e):
    """Here for the fixture's sake too: ``ops/power_retention`` at [2, 8192,
    40 | 8 x 128] bfloat16 (the 65 lane tiles of the feature map, the
    [8320, 128] float32 state and phi(K) in VMEM, the chunk's decay from SMEM),
    and no temporary of the feature map's size in HBM (phi(Q) alone would be
    10.8 GB a layer)."""
    from deepdfa_tpu.ops.power_retention import power_retention

    b, s, h, hk, d = 2, 8192, 40, 8, 128
    shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(dims, dtype, sharding=one_v5e)
    compiled = jax.jit(functools.partial(power_retention, chunk=128, interpret=False)).trace(
        shape(b, s, h * d), shape(b, s, hk * d), shape(b, s, hk * d),
        shape(b, s, hk, dtype=jnp.float32), shape(b, s, dtype=jnp.bool_)).lower(
            lowering_platforms=("tpu",)).compile()
    assert "power_retention_fwd" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


def test_the_grouped_query_attention_kernel_compiles_for_the_v5e_at_ccas_size(one_v5e):
    """Here for the fixture's sake too: ``ops/gqa_attention`` as
    ``llm/zaya.py`` calls it, at [2, 8192, 8 | 2 x 128] bfloat16 (four query
    heads a key/value head: a grid step's tile is 512 lanes wide), and
    nothing near a query block's float32 scores among the temporaries."""
    from deepdfa_tpu.ops.gqa_attention import gqa_attention

    b, s, h, hk = 2, 8192, 8, 2
    shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(dims, dtype, sharding=one_v5e)
    compiled = jax.jit(functools.partial(gqa_attention, num_kv_heads=hk)).trace(
        shape(b, s, h * 128), shape(b, s, hk * 128), shape(b, s, hk * 128),
        shape(b, s, dtype=jnp.bool_),
    ).lower(lowering_platforms=("tpu",)).compile()
    assert "gqa_attention_fwd" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**20
