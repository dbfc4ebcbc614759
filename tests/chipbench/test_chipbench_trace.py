"""Trace reduction: intervals, busy and idle time, idle gaps named by
the host span open over them, and the per-layer readers, on a
synthetic trace and on a trimmed trace recorded on a TPU v5e
(`fixtures/`, written by `benchmarks/chip/tools/record_trace.py`)."""
from __future__ import annotations

import gzip
import json

import pytest

import chipbench_common  # noqa: F401  (puts the harness on the path)
from harness import spec as S, trace as T
from harness.readers import kernel_roofline, step_ms

PAT = S.load_json(S.BENCH_DIR / "patterns.json")
OPS, MODS = PAT["ops_line"], PAT["modules_line"]


def _trace():
    """One device: a decode program 100-150 ns holding a decode kernel
    110-130 and two ops after it; a prefill program 200-300 holding its
    kernel."""
    dk = ('%paged_decode_attention.3 = bf16[8,4,8,128] custom-call(s32[8,16] '
          '%p), custom_call_target="tpu_custom_call"')
    pk = ('%paged_prefill_attention.8 = bf16[8,4,64,128] custom-call(s32[8,16] '
          '%p), custom_call_target="tpu_custom_call"')
    ops = [["%fusion.1 = bf16[8,2048] fusion(%a)", 100, 10], [dk, 110, 20],
           ["%fusion.2 = f32[8,16] fusion(%b)", 130, 10],
           ["%fusion.3 = f32[8,16] fusion(%c)", 140, 10], [pk, 200, 100]]
    mods = [["jit_decode(1)", 100, 50], ["jit_prefill_chunk(2)", 200, 100]]
    host = [["bench.step", 90, 220], ["bench.decode_call", 95, 20],
            ["bench.drain", 310, 90]]
    return {"devices": {"/device:TPU:0": {OPS: ops, MODS: mods}},
            "host": host}


def test_union_merges_overlaps():
    assert T.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_busy_and_window():
    busy, window = T.busy_share(_trace(), OPS)
    assert busy == pytest.approx(150e-9)      # 100-150 and 200-300
    assert window == pytest.approx(310e-9)    # host spans 90-400


def test_idle_gaps_named_by_innermost_host_span():
    gaps = T.idle_gaps(_trace(), OPS)
    assert gaps[0] == ["bench.drain", pytest.approx(100e-9)]     # 300-400
    assert ["bench.step", pytest.approx(50e-9)] in gaps          # 150-200
    assert ["bench.decode_call", pytest.approx(10e-9)] in gaps   # 90-100


def test_top_ops_sum_names():
    top = T.top_ops(_trace(), OPS)
    assert top[0] == ["%paged_prefill_attention.8", pytest.approx(1e-7)]


def test_loop_holding_other_ops_is_not_counted_twice():
    tr = _trace()
    tr["devices"]["/device:TPU:0"][OPS].insert(
        0, ["%while.5 = (s32[]) while(%t)", 100, 50])
    names = [n for n, _ in T.top_ops(tr, OPS)]
    assert "%while.5" not in names and "%paged_decode_attention.3" in names


def _ctx(trace, decode, prefill):
    return dict(trace=trace, patterns=PAT,
                record={"decode": decode, "prefill": prefill},
                peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9})


def test_step_ms_and_roofline_readers():
    ctx = _ctx(_trace(), decode=[[3]], prefill=[[(0, 4)]])
    assert step_ms(ctx, "decode_step", 1) == pytest.approx(50e-6)
    assert step_ms(ctx, "prefill_step", 1) == pytest.approx(100e-6)
    # 2e-8 s of bytes-bound work in a 20 ns kernel: the roofline itself
    assert kernel_roofline(ctx, "paged_decode_kernel", 0, 20.0) == \
        pytest.approx(100.0)


def test_reader_without_calls_reads_nothing_and_without_events_fails():
    ctx = _ctx(_trace(), decode=[], prefill=[])
    assert step_ms(ctx, "decode_step", 0) is None
    empty = {"devices": {"/device:TPU:0": {OPS: [], MODS: []}},
             "host": _trace()["host"]}
    with pytest.raises(RuntimeError):
        step_ms(_ctx(empty, [[1]], []), "decode_step", 1)
    with pytest.raises(RuntimeError):
        kernel_roofline(_ctx(empty, [[1]], []), "paged_decode_kernel", 1, 1)


def _recorded(name):
    from chipbench_common import ROOT
    path = ROOT / "tests" / "chipbench" / "fixtures" / f"trace_{name}.json.gz"
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_recorded_trace_reduces():
    tr = _recorded("chat")
    assert T.planes(tr) == ["/device:TPU:0"]
    busy, window = T.busy_share(tr, OPS)
    assert 0 < busy <= window
    # the programs and the prefill kernel are found by their patterns
    ns, n = T.sum_matching(tr, MODS, PAT["prefill_step"])
    assert n >= 2 and ns > 0
    kns, kn = T.sum_matching(tr, OPS, PAT["paged_prefill_kernel"])
    assert kn >= 24 and 0 < kns < ns
    dns, dn = T.sum_matching(tr, MODS, PAT["decode_step"])
    kns, kn = T.sum_matching(tr, OPS, PAT["paged_decode_kernel"])
    assert dn >= 1 and kn >= 24 and 0 < kns < dns
    # the loop around the layers is no operation of its own
    names = [n for n, _ in T.top_ops(tr, OPS)]
    assert not any(n.startswith("%while") for n in names)
    assert any(n.startswith("%paged_prefill_attention") for n in names)
    assert {g[0] for g in T.idle_gaps(tr, OPS)} <= {
        "bench.submit", "bench.step", "bench.drain", "bench.prefill_call",
        "bench.decode_call", "none"}
    ms = step_ms(_ctx(tr, decode=[], prefill=[[(0, 64)]] * n),
                 "prefill_step", n)
    assert ms == pytest.approx(ns / 1e6 / n)
