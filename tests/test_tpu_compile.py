"""The served path's kernels compile for a TPU v5e at real widths.

The TPU compiler is installed wherever JAX is; it compiles for a v5e that
is described, not attached, so these tests need no chip. They catch what
interpret mode cannot: blocks that break the (8, 128) tiling rule, layouts
Mosaic refuses, programs that do not fit the chip's memory.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file.

Shapes are the yi-9b ``serve_stage()`` preset: 8 sequences, 32 query / 4
KV heads of 128, 16-token pages, its pool and its 64-page block tables.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import yi_9b
from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.scope_copy.ops import gather_pages, scatter_pages
from repro.models import build_model
from repro.serving.paged_model import paged_decode_step

V5E_HBM_BYTES = 16 * 2**30

B = yi_9b.SERVE_STAGE_MAX_ACTIVE
L = yi_9b.SERVE_STAGE_LAYERS
P = yi_9b.SERVE_STAGE_PAGES
T = yi_9b.SERVE_STAGE_PAGE_TOKENS
MAXP = yi_9b.SERVE_STAGE_MAX_PAGES_PER_SEQ
HQ, HKV, D = (yi_9b.CONFIG.num_heads, yi_9b.CONFIG.num_kv_heads,
              yi_9b.CONFIG.head_dim)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def spec(one_chip):
    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _fits(compiled):
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert used <= V5E_HBM_BYTES, ma
    return ma


def test_paged_attention_compiles_at_serve_stage(spec):
    i32 = jnp.int32
    pool = spec((L, P, HKV, T, D), jnp.bfloat16)
    compiled = paged_attention.lower(
        spec((B, HQ, D), jnp.bfloat16), pool, pool, spec((B, MAXP), i32),
        spec((B,), i32), spec((P,), i32), spec((3,), i32), spec((P,), i32),
        backend="tpu", layer=L - 1).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the layer is read in place: no copy of the pool
    assert _fits(compiled).temp_size_in_bytes < P * HKV * T * D * 2


@pytest.mark.parametrize("kernel", ["gather", "scatter"])
def test_scope_copy_compiles_at_page_row(spec, kernel):
    """One page row of the (L·P, Hkv·T, D) view of the pool, moved for a
    whole 64-page sequence."""
    pool = spec((L * P, HKV * T, D), jnp.bfloat16)
    pages = spec((MAXP,), jnp.int32)
    if kernel == "gather":
        lowered = gather_pages.lower(pool, pages, backend="tpu")
    else:
        buf = spec((MAXP, HKV * T, D), jnp.bfloat16)
        lowered = scatter_pages.lower(pool, pages, buf, backend="tpu")
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_decode_step_writes_tokens_without_pool_relayout(spec, one_chip):
    """Two layers of the stage at full width: the step must hold the
    kernel, and its temporaries must stay far below one layer-stacked
    pool — a row scatter of each new token made the compiler relayout the
    whole pool around every layer."""
    from dataclasses import replace

    cfg = replace(yi_9b.serve_stage()[0], num_layers=2)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        build_model(cfg).param_shapes())
    i32 = jnp.int32
    pool = spec((2, P, HKV, T, D), jnp.bfloat16)
    compiled = paged_decode_step.lower(
        cfg, params, spec((B,), i32), spec((B,), i32), spec((B, MAXP), i32),
        spec((B,), i32), pool, pool, spec((P,), i32), spec((3,), i32),
        spec((P,), i32), backend="tpu").compile()
    assert "tpu_custom_call" in compiled.as_text()
    pool_bytes = 2 * P * HKV * T * D * 2
    assert _fits(compiled).temp_size_in_bytes < pool_bytes // 4
