"""Compile-only checks of the fused paged kernels for a TPU v5e.

The TPU compiler is installed beside JAX, and it compiles for a chip
that is described rather than attached.  These tests lower the decode
and chunk-prefill kernels at internlm2-1.8b serving widths (16 query
heads over 8 KV heads, head_dim 128, page 16, a 2048-token block table)
and compile them for one v5e chip: Mosaic's tiling rules, VMEM limits
and scalar-prefetch index maps are checked here, which interpret mode
never does.  Nothing runs, so results are the interpret-mode tests'
business.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU
library, and pytest-xdist workers import every test file.
"""
from __future__ import annotations

import os

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention.ops import paged_decode_attention
from repro.kernels.paged_prefill.ops import paged_prefill_attention

# internlm2-1.8b attention widths at the serving shape of chip_smoke.py
B, HQ, HKV, HD = 8, 16, 8, 128
PAGE, MAX_SEQ = 16, 2048
MP = MAX_SEQ // PAGE
NUM_PAGES = B * MP + 1                    # pool + null page


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
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _arena_args(sharding, kv_dtype):
    """Shapes of one layer's K/V arena (+ scale banks when quantized)."""
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    kv = S((NUM_PAGES, PAGE, HKV, HD), kv_dtype)
    scales = ()
    if kv_dtype != jnp.bfloat16:
        scales = (S((NUM_PAGES, PAGE, HKV), jnp.float32),) * 2
    return S, (kv, kv, *scales)


def _scales(sc):
    return dict(zip(("k_scale", "v_scale"), sc))


@pytest.mark.parametrize("kv_dtype,ppb,partials", [
    (jnp.bfloat16, 1, False),
    (jnp.bfloat16, 8, False),
    (jnp.bfloat16, 2, True),              # the sharded walk's summary mode
    (jnp.int8, 4, False),
])
def test_paged_decode_compiles_for_v5e(one_chip, no_persistent_cache,
                                       kv_dtype, ppb, partials):
    S, arena = _arena_args(one_chip, kv_dtype)

    def step(q, bt, pos, k, v, *sc):
        return paged_decode_attention(q, k, v, bt, pos, pages_per_block=ppb,
                                      partials=partials, interpret=False,
                                      **_scales(sc))

    compiled = jax.jit(step).lower(
        S((B, HQ, HD), jnp.bfloat16), S((B, MP), jnp.int32),
        S((B,), jnp.int32), *arena).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kv_dtype,chunk,ppb", [
    (jnp.bfloat16, 64, 1),
    (jnp.bfloat16, 256, 4),
    (jnp.int8, 128, 2),
])
def test_paged_prefill_compiles_for_v5e(one_chip, no_persistent_cache,
                                        kv_dtype, chunk, ppb):
    S, arena = _arena_args(one_chip, kv_dtype)

    def step(q, bt, start, clen, k, v, *sc):
        return paged_prefill_attention(q, k, v, bt, start, clen,
                                       pages_per_block=ppb, interpret=False,
                                       **_scales(sc))

    compiled = jax.jit(step).lower(
        S((B, chunk, HQ, HD), jnp.bfloat16), S((B, MP), jnp.int32),
        S((B,), jnp.int32), S((B,), jnp.int32), *arena).compile()
    assert "tpu_custom_call" in compiled.as_text()
