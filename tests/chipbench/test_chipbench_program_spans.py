"""The readers of the program's own spans (`harness/program.py` and the
four metrics that use it): on a synthetic trace with synthetic spans,
answers known by hand; on the trace recorded on a TPU v5e with tick
spans laid on its `bench.step` spans; and the ways they refuse to read
part of a window."""
from __future__ import annotations

import gzip
import json

import pytest

import chipbench_common  # noqa: F401  (puts the harness on the path)
from chipbench_common import ROOT
from harness import program as P, spec as S, trace as T
from repro.serve import tracing
from repro.serve.tracing import Span

PAT = S.load_json(S.BENCH_DIR / "patterns.json")
OPS = PAT["ops_line"]
METRICS = ("engine_tick_ms", "queue_wait_p90_ms", "active_row_share",
           "idle_host_share")


def _trace():
    """Window 50-600 ns (from the benchmark's spans); device busy
    100-150, 200-300, 450-600, so idle 50-100, 150-200, 300-450."""
    ops = [["%fusion.1 = f32[8] fusion(%a)", 100, 50],
           ["%fusion.2 = f32[8] fusion(%b)", 200, 100],
           ["%fusion.3 = f32[8] fusion(%c)", 450, 150]]
    host = [["bench.submit", 50, 10], ["bench.step", 100, 200],
            ["bench.step", 400, 200]]
    return {"devices": {"/device:TPU:0": {OPS: ops}}, "host": host}


def _span(i, name, start, end, tick, parent=None, engine=1, uid=None,
          **attrs):
    return Span(i, parent, name, start, end, engine, tick, uid, attrs)


def _spans():
    """Tick 0 at 1000-1190 on the program's clock (100-290 on the
    trace's), its readback 1050-1100 (150-200); tick 1 at 5000-5150
    (400-550), its admission 5000-5030 (400-430). Tick 2 ran after the
    close; engine 0 is an earlier engine of the process."""
    return [
        _span(1, "engine.step", 0, 10, 0, engine=0),
        _span(2, "engine.step", 1000, 1190, 0),
        _span(3, "engine.prefill.dispatch", 1010, 1040, 0, 2, rows=1,
              batch=4, width=64, served=[(0, 20)]),
        _span(4, "engine.decode.dispatch", 1040, 1050, 0, 2, rows=2,
              batch=4, positions=[5, 9]),
        _span(5, "engine.decode.readback", 1050, 1100, 0, 2),
        _span(6, "engine.step", 5000, 5150, 1),
        _span(7, "engine.admit", 5000, 5030, 1, 6, admitted=3),
        _span(8, "engine.queue", 100, 1100, 1, 7, uid=7),
        _span(9, "engine.queue", 2000, 4000, 1, 7, uid=8),
        _span(10, "engine.queue", 4000, 7000, 1, 7, uid=9),
        _span(11, "engine.step", 9000, 9400, 2),
        _span(12, "engine.decode.dispatch", 9000, 9100, 2, 11, rows=4,
              batch=4, positions=[1, 2, 3, 4]),
        _span(13, "engine.queue", 0, 9000, 2, 11, uid=10),
    ]


@pytest.fixture
def ring(monkeypatch):
    """The program's ring replaced by the spans a test gives it."""
    box = {"spans": _spans()}
    monkeypatch.setattr(tracing, "spans", lambda: list(box["spans"]))
    return box


def _ctx(ticks=2, trace=None):
    return dict(trace=trace or _trace(), patterns=PAT, ticks=ticks)


def _read(name, ctx):
    return S.metric_reader(name).read(ctx)


def test_readers_on_synthetic_spans(ring):
    ctx = _ctx()
    assert _read("engine_tick_ms", ctx) == pytest.approx(170e-6)
    # waits of 1000, 2000 and 3000 ns end in the window's ticks
    assert _read("queue_wait_p90_ms", ctx) == pytest.approx(2800e-6)
    assert _read("active_row_share", ctx) == pytest.approx(100 * 3 / 8)
    # idle inside the aligned ticks: 150-200 and 400-450, of 550 ns
    assert _read("idle_host_share", ctx) == pytest.approx(100 * 100 / 550)
    assert _read("idle_share", ctx) == pytest.approx(100 * 250 / 550)


def test_idle_put_down_to_the_innermost_phase(ring):
    ctx = _ctx()
    split = P.idle_by_phase(ctx, P.window(ctx))
    assert split == pytest.approx({
        "engine.decode.readback": 50e-9, "engine.admit": 30e-9,
        "engine.step": 20e-9, "engine.prefill.dispatch": 0.0,
        "engine.decode.dispatch": 0.0, "outside engine.step": 150e-9})


def test_readers_read_nothing_without_program_spans(ring):
    ring["spans"] = []
    assert all(_read(m, _ctx()) is None for m in METRICS)
    ring["spans"] = _spans()
    assert all(_read(m, _ctx(ticks=0)) is None for m in METRICS)


def test_a_window_tick_missing_from_the_ring_raises(ring):
    ring["spans"] = [s for s in _spans() if s.tick != 1]
    for m in METRICS:
        with pytest.raises(RuntimeError, match="missing"):
            _read(m, _ctx())


def test_bench_steps_not_one_per_tick_raise(ring):
    tr = _trace()
    tr["host"].append(["bench.step", 700, 10])
    with pytest.raises(RuntimeError, match="bench.step"):
        _read("idle_host_share", _ctx(trace=tr))


def test_recorded_trace_idle_inside_ticks_is_idle_inside_bench_steps(ring):
    with gzip.open(ROOT / "tests" / "chipbench" / "fixtures"
                   / "trace_chat.json.gz", "rt") as f:
        tr = json.load(f)
    bench = sorted((s, s + d) for n, s, d in tr["host"] if n == "bench.step")
    off = 123_456_789         # the program's clock against the trace's
    ring["spans"] = [_span(k + 1, "engine.step", s - off, e - off, k)
                     for k, (s, e) in enumerate(bench)]
    lo, hi = T.span_of(tr)
    busy = T.union((s, s + d) for _, s, d in
                   T.device_line(tr, T.planes(tr)[0], OPS))
    idle_in = sum((e - s) - sum(max(0, min(e, be) - max(s, bs))
                                for bs, be in busy) for s, e in bench)
    got = _read("idle_host_share", _ctx(ticks=len(bench), trace=tr))
    assert idle_in > 0
    assert got == pytest.approx(100 * idle_in / (hi - lo))
    assert got <= _read("idle_share", _ctx(ticks=len(bench), trace=tr))
