"""The `paged_live_block_share` reader: on synthetic spans whose
dispatches count the page blocks the paged kernels walk, the share
known by hand; on spans of a program that does not count them,
nothing, while the other readers of the same spans still read."""
from __future__ import annotations

import pytest

import chipbench_common  # noqa: F401  (puts the harness on the path)
from harness import spec as S
from repro.serve import tracing
from repro.serve.tracing import Span

PAT = S.load_json(S.BENCH_DIR / "patterns.json")
OPS = PAT["ops_line"]


def _trace():
    """Window 50-600 ns (from the benchmark's spans), the device busy
    in part of it."""
    ops = [["%fusion.1 = f32[8] fusion(%a)", 100, 50],
           ["%fusion.2 = f32[8] fusion(%b)", 450, 150]]
    host = [["bench.submit", 50, 10], ["bench.step", 100, 200],
            ["bench.step", 400, 200]]
    return {"devices": {"/device:TPU:0": {OPS: ops}}, "host": host}


def _span(i, name, start, end, tick, parent=None, engine=1, **attrs):
    return Span(i, parent, name, start, end, engine, tick, None, attrs)


def _spans():
    """Ticks 0 and 1 lie in the window; tick 2 ran after the close and
    engine 0 is an earlier engine of the process, so neither counts."""
    return [
        _span(1, "engine.step", 0, 10, 0, engine=0),
        _span(2, "engine.prefill.dispatch", 1, 5, 0, 1, engine=0,
              rows=4, batch=4, blocks=40, slots=40),
        _span(3, "engine.step", 1000, 1190, 0),
        _span(4, "engine.prefill.dispatch", 1010, 1040, 0, 3, rows=1,
              batch=4, blocks=2, slots=40),
        _span(5, "engine.decode.dispatch", 1040, 1050, 0, 3, rows=2,
              batch=4, blocks=5, slots=40),
        _span(6, "engine.step", 5000, 5150, 1),
        _span(7, "engine.verify.dispatch", 5010, 5100, 1, 6, rows=1,
              batch=2, blocks=3, slots=20),
        _span(8, "engine.step", 9000, 9400, 2),
        _span(9, "engine.decode.dispatch", 9000, 9100, 2, 8, rows=4,
              batch=4, blocks=40, slots=40),
    ]


@pytest.fixture
def ring(monkeypatch):
    """The program's ring replaced by the spans a test gives it."""
    box = {"spans": _spans()}
    monkeypatch.setattr(tracing, "spans", lambda: list(box["spans"]))
    return box


def _read(name):
    ctx = dict(trace=_trace(), patterns=PAT, ticks=2)
    return S.metric_reader(name).read(ctx)


def test_live_block_share_sums_the_window_dispatches(ring):
    # blocks 2 + 5 + 3 of slots 40 + 40 + 20: prefill, decode and verify
    assert _read("paged_live_block_share") == pytest.approx(100 * 10 / 100)


@pytest.mark.parametrize("drop", [("blocks", "slots"), ("blocks",)])
def test_live_block_share_reads_nothing_from_spans_without_blocks(ring,
                                                                  drop):
    """A program older than the counts (the dispatch spans carry only
    `rows` and `batch`) reads nothing, while the rest still read."""
    ring["spans"] = [
        s._replace(attrs={k: v for k, v in s.attrs.items()
                          if k not in drop})
        for s in _spans()]
    assert _read("paged_live_block_share") is None
    assert _read("active_row_share") == pytest.approx(100 * 4 / 10)
