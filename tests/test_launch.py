"""launch/ machinery: HLO analyzer (trip-count scaling, wire bytes,
traffic proxy), roofline math, cell builders."""
from __future__ import annotations

import pytest

from repro.launch import hlo_analysis as H
from repro.launch.roofline import RooflineRow, row_from_record
from repro.configs import SHAPES, get_arch
from repro.launch.cells import model_flops, active_params


SYNTH_HLO = """\
HloModule jit_step, is_scheduled=true

%inner_body (p: (s32[], f32[8,64])) -> (s32[], f32[8,64]) {
  %p = (s32[], f32[8,64]) parameter(0)
  %w = f32[64,64]{1,0} parameter(1)
  %x = f32[8,64]{1,0} get-tuple-element(%p), index=1
  %ag = f32[8,64]{1,0} all-gather(%x), dimensions={1}, replica_groups=[2,4]<=[8]
  %dot = f32[8,64]{1,0} dot(%ag, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %t = (s32[], f32[8,64]) tuple(%x, %dot)
}

%inner_cond (pc: (s32[], f32[8,64])) -> pred[] {
  %pc = (s32[], f32[8,64]) parameter(0)
  ROOT %lt = pred[] compare(%pc, %pc), direction=LT
}

ENTRY %main (a: f32[8,64]) -> f32[8,64] {
  %a = f32[8,64]{1,0} parameter(0)
  %init = (s32[], f32[8,64]) tuple(%a, %a)
  %loop = (s32[], f32[8,64]) while(%init), condition=%inner_cond, body=%inner_body, backend_config={"known_trip_count":{"n":"6"}}
  %ar = f32[8,64]{1,0} all-reduce(%a), replica_groups=[4,2]<=[8], to_apply=%inner_cond
  ROOT %out = f32[8,64]{1,0} get-tuple-element(%loop), index=1
}
"""


def test_shape_bytes():
    assert H.shape_bytes("f32[8,64]{1,0}") == 8 * 64 * 4
    assert H.shape_bytes("(s32[], f32[2,2])") == 4 + 16
    assert H.shape_bytes("bf16[10]") == 20


def test_multiplier_propagation_and_flop_scaling():
    prog = H.parse_hlo(SYNTH_HLO)
    assert prog.entry == "main"
    assert prog.multipliers["inner_body"] == 6.0
    assert prog.multipliers["main"] == 1.0
    s = H.summarize(SYNTH_HLO)
    # dot: 2 * 8*64 * 64 flops, x6 trips
    assert s.flops == 6 * 2 * 8 * 64 * 64
    assert s.raw_flops == 2 * 8 * 64 * 64


def test_wire_bytes_accounting():
    s = H.summarize(SYNTH_HLO)
    r = 8 * 64 * 4
    # all-gather in the loop: group 4, x6 trips
    assert s.collective_bytes["all-gather"] == pytest.approx(6 * r * 3 / 4)
    # entry all-reduce: group 2 -> 2*(1/2)*r
    assert s.collective_bytes["all-reduce"] == pytest.approx(2 * r * 1 / 2)


def test_roofline_row_math():
    rec = {
        "arch": "x", "shape": "train_4k", "mesh": "single",
        "mesh_shape": {"data": 16, "model": 16}, "ok": True,
        "hlo": {
            "flops_per_device": 197e12,          # exactly 1s compute
            "bytes_read_per_device": 819e9 / 2,
            "bytes_written_per_device": 819e9 / 2,   # exactly 1s memory
            "collective_bytes_per_device": {"all-reduce": 100e9},  # 2s
        },
        "memory_analysis": {"argument_size_in_bytes": 1e9,
                            "temp_size_in_bytes": 2e9},
        "model_flops": 197e12 * 256 * 0.5,
    }
    row = row_from_record(rec)
    assert row.chips == 256
    assert row.compute_s == pytest.approx(1.0)
    assert row.memory_s == pytest.approx(1.0)
    assert row.collective_s == pytest.approx(2.0)
    assert row.dominant == "collective"
    assert row.step_s == pytest.approx(2.0)
    assert row.useful_ratio == pytest.approx(0.5)
    assert row.roofline_fraction == pytest.approx(0.25)
    assert row.mem_gb_per_dev == pytest.approx(3.0)


def test_model_flops_moe_counts_active_only():
    moe = get_arch("qwen3-moe-30b-a3b").model
    n_active = active_params(moe)
    n_total = 30.5e9
    assert n_active < 4.5e9                     # ~3B active of 30B total
    f = model_flops(moe, SHAPES["train_4k"])
    tokens = 256 * 4096
    assert f > 6 * n_active * tokens            # attention term added


def test_model_flops_decode_uses_one_token():
    cfg = get_arch("yi-9b").model
    f_dec = model_flops(cfg, SHAPES["decode_32k"])
    f_pre = model_flops(cfg, SHAPES["prefill_32k"])
    assert f_dec < f_pre / 1000                 # decode is 1 token/seq


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_follows_env_else_fixed_checkout_path(
        monkeypatch, tmp_path, from_env):
    """`JAX_COMPILATION_CACHE_DIR` places the persistent cache; without
    it the cache sits at one fixed, git-ignored path in the checkout."""
    from pathlib import Path

    import jax
    from repro.utils import compile_cache as cc

    repo = Path(__file__).resolve().parents[1]
    if from_env:
        want = str(tmp_path / "cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    else:
        want = str(repo / ".jax_cache")
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert cc.use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
