"""Compile-only checks of the fused paged kernels for a TPU v5e.

The TPU compiler is installed beside JAX, and it compiles for a chip
that is described rather than attached.  These tests lower the decode
and chunk-prefill kernels at internlm2-1.8b serving widths (16 query
heads over 8 KV heads, head_dim 128, page 16, a 2048-token block table),
and the prefill kernel at each benchmark cell's own geometry, and
compile them for one v5e chip: Mosaic's tiling rules, VMEM limits
and scalar-prefetch index maps are checked here, which interpret mode
never does.  Nothing runs, so results are the interpret-mode tests'
business.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU
library, and pytest-xdist workers import every test file.
"""
from __future__ import annotations

import json
import os
import re
from pathlib import Path

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention.ops import paged_decode_attention
from repro.kernels.paged_prefill.ops import paged_prefill_attention

# the names by which the on-chip benchmark finds the steps and kernels
# in a profiler trace
PATTERNS = json.loads((Path(__file__).resolve().parents[1] / "benchmarks"
                       / "chip" / "patterns.json").read_text())

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


# the benchmark cells' prefill geometries (benchmarks/chip/cells/):
# (batch, query heads, KV heads, block-table width, arena pages + null)
CELL_GEOMETRIES = {
    "internlm2-1.8b.chat": (32, 16, 8, 2560 // PAGE, 3584 + 1),
    "yi-9b-l24.docqa": (16, 32, 4, 3648 // PAGE, 3648 + 1),
}


def _arena_args(sharding, kv_dtype, hkv=HKV, num_pages=NUM_PAGES):
    """Shapes of one layer's K/V arena (+ scale banks when quantized)."""
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    kv = S((num_pages, PAGE, hkv, HD), kv_dtype)
    scales = ()
    if kv_dtype != jnp.bfloat16:
        scales = (S((num_pages, PAGE, hkv), jnp.float32),) * 2
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


@pytest.mark.parametrize("kv_dtype,chunk,ppb,cell", [
    (jnp.bfloat16, 64, 1, None),
    (jnp.bfloat16, 256, 4, None),
    (jnp.int8, 128, 2, None),
    (jnp.bfloat16, 64, 1, "internlm2-1.8b.chat"),
    (jnp.bfloat16, 64, 1, "yi-9b-l24.docqa"),
])
def test_paged_prefill_compiles_for_v5e(one_chip, no_persistent_cache,
                                        kv_dtype, chunk, ppb, cell):
    b, hq, hkv, mp, pages = CELL_GEOMETRIES.get(
        cell, (B, HQ, HKV, MP, NUM_PAGES))
    S, arena = _arena_args(one_chip, kv_dtype, hkv, pages)

    def step(q, bt, start, clen, k, v, *sc):
        return paged_prefill_attention(q, k, v, bt, start, clen,
                                       pages_per_block=ppb, interpret=False,
                                       **_scales(sc))

    compiled = jax.jit(step).lower(
        S((b, chunk, hq, HD), jnp.bfloat16), S((b, mp), jnp.int32),
        S((b,), jnp.int32), S((b,), jnp.int32), *arena).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _custom_calls(hlo: str) -> list[str]:
    """The compiled module's kernel lines as a trace names its events:
    the instruction without indentation or `ROOT`."""
    return [ln.strip().removeprefix("ROOT ") for ln in hlo.splitlines()
            if "custom_call_target=\"tpu_custom_call\"" in ln]


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_kernel_names_match_trace_patterns(one_chip, no_persistent_cache,
                                           which):
    """Inside the engine step's name scope, each kernel compiles to a
    custom call named as `patterns.json` expects, and matches only its
    own pattern."""
    S, arena = _arena_args(one_chip, jnp.bfloat16)
    if which == "decode":
        @jax.jit
        @jax.named_scope("decode_step")
        def step(q, bt, pos, k, v):
            return paged_decode_attention(q, k, v, bt, pos, interpret=False)
        args = (S((B, HQ, HD), jnp.bfloat16), S((B, MP), jnp.int32),
                S((B,), jnp.int32), *arena)
    else:
        @jax.jit
        @jax.named_scope("prefill_step")
        def step(q, bt, start, clen, k, v):
            return paged_prefill_attention(q, k, v, bt, start, clen,
                                           interpret=False)
        args = (S((B, 64, HQ, HD), jnp.bfloat16), S((B, MP), jnp.int32),
                S((B,), jnp.int32), S((B,), jnp.int32), *arena)
    calls = _custom_calls(step.lower(*args).compile().as_text())
    own = re.compile(PATTERNS[f"paged_{which}_kernel"])
    other = re.compile(PATTERNS["paged_prefill_kernel" if which == "decode"
                                else "paged_decode_kernel"])
    assert calls and all(own.search(c) for c in calls), calls
    assert not any(other.search(c) for c in calls)


def test_engine_step_programs_keep_their_trace_names(one_chip,
                                                     no_persistent_cache):
    """The engine's jitted steps, compiled for the chip, are modules the
    trace patterns find: `decode_step` and `prefill_step` each match
    their own program only."""
    from repro.models import registry
    from repro.models.config import ModelConfig
    from repro.serve.sampling import greedy_state
    from repro.serve.serve_step import make_paged_serve_fns

    cfg = ModelConfig(name="tiny-dense", family="dense", num_layers=2,
                      d_model=64, vocab_size=128, num_heads=4,
                      num_kv_heads=2, head_dim=16, d_ff=128, attn_chunk=32,
                      max_seq=64)
    fam = registry.get_family(cfg)
    b, page, mp, c = 2, 8, 8, 16
    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = on_chip(jax.eval_shape(lambda: fam.init(jax.random.key(0), cfg)))
    arena = on_chip(jax.eval_shape(
        lambda: fam.init_paged_cache(cfg, b * mp + 1, page, b)))
    st = on_chip(jax.eval_shape(lambda: greedy_state(b)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_chip)
    prefill_fn, decode_fn = make_paged_serve_fns(cfg)
    modules = {
        "decode_step": decode_fn.lower(params, arena, i32(b, mp), i32(b),
                                       i32(b), st),
        "prefill_step": prefill_fn.lower(params, {"tokens": i32(b, c)},
                                         arena, i32(b, mp), i32(b), i32(b),
                                         st)}
    names = {k: re.match(r"HloModule (\S+?),",
                         low.compile().as_text()).group(1)
             for k, low in modules.items()}
    for key, name in names.items():
        hits = {k for k in names if re.search(PATTERNS[k], name)}
        assert hits == {key}, (name, hits)
