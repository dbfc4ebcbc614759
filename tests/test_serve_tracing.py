"""The serving program's spans (serve/tracing.py) on tiny engines: ticks
and their phases, queue waits, the rows each dispatch served, the ring's
bound, and the spans' place in a profiler trace."""
from __future__ import annotations

import glob
import os
import sys
from pathlib import Path

import numpy as np
import pytest
import jax

from repro.models import registry
from repro.serve import ServingEngine, Request, tracing

from conftest import TINY

BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"

PHASES = {"engine.admit", "engine.tier_prefetch", "engine.prefill.build",
          "engine.prefill.dispatch", "engine.prefill.readback",
          "engine.prefill.emit", "engine.decode.build",
          "engine.decode.dispatch", "engine.decode.readback",
          "engine.decode.emit", "engine.retire"}


@pytest.fixture(scope="module")
def params():
    cfg = TINY["dense"]
    return registry.get_family(cfg).init(jax.random.key(0), cfg)


def _engine(params, **kw):
    kw = dict(dict(max_batch=4, max_seq=64, page_size=8), **kw)
    return ServingEngine(TINY["dense"], params, **kw)


def _submit(eng, n, plen=20, max_new=6, seed=10):
    rng = np.random.default_rng(seed)
    for u in range(n):
        eng.submit(Request(uid=u, max_new_tokens=max_new, prompt=rng.integers(
            0, TINY["dense"].vocab_size, plen).astype(np.int32)))


def _mine(eng):
    return [s for s in tracing.spans() if s.engine == eng.trace_id]


def test_ticks_numbered_from_zero_with_phases_nested(params):
    eng = _engine(params)
    _submit(eng, 3)
    eng.run()
    spans = _mine(eng)
    steps = {s.tick: s for s in spans if s.name == "engine.step"}
    assert sorted(steps) == list(range(eng.steps))
    assert sum(s.name == "engine.step" for s in spans) == eng.steps
    by_id = {s.id: s for s in spans}
    assert PHASES <= {s.name for s in spans}
    for s in spans:
        if s.name in PHASES:
            up = by_id[s.parent]
            assert up.name == "engine.step" and up.tick == s.tick
            assert up.start_ns <= s.start_ns <= s.end_ns <= up.end_ns


def test_each_admission_has_one_queue_span_and_a_preempted_request_two(
        params, monkeypatch):
    eng = _engine(params, pool_pages=16, high_watermark=0.5)
    preempted = []
    orig = eng._preempt_slot
    monkeypatch.setattr(eng, "_preempt_slot", lambda idx, victim: (
        preempted.append(victim.request.uid), orig(idx, victim)))
    _submit(eng, 3)
    eng.run()
    assert preempted, "the watermark never preempted"
    queue = [s for s in _mine(eng) if s.name == "engine.queue"]
    per_uid = {u: sum(s.uid == u for s in queue) for u in range(3)}
    assert per_uid == {u: 1 + preempted.count(u) for u in range(3)}
    assert max(per_uid.values()) >= 2
    admits = {s.id: s for s in _mine(eng) if s.name == "engine.admit"}
    for s in queue:
        assert s.start_ns <= s.end_ns
        assert admits[s.parent].tick == s.tick      # ends at admission


def test_dispatch_spans_serve_what_the_recorder_records(params):
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    from harness.serve import Recorder
    eng = _engine(params, prefill_chunk=16)
    rec = Recorder(eng)
    _submit(eng, 4, plen=37, max_new=5)
    eng.run()
    spans = sorted(_mine(eng), key=lambda s: s.start_ns)
    pre = [s.attrs for s in spans if s.name == "engine.prefill.dispatch"]
    dec = [s.attrs for s in spans if s.name == "engine.decode.dispatch"]
    assert [a["rows"] for a in pre] == [len(r) for r in rec.prefill]
    assert [a["rows"] for a in dec] == [len(r) for r in rec.decode]
    assert all(a["batch"] == 4 for a in pre + dec)
    assert max(a["rows"] for a in pre) > 1 and max(a["rows"] for a in dec) > 1


@pytest.mark.parametrize("ppb", [1, 3])
def test_dispatch_spans_count_the_page_blocks_the_walk_computes(params,
                                                                  ppb):
    """`blocks` is the page blocks the kernels compute, each row up to
    the block holding its last query position, counted here by hand from
    each call's own start/chunk_len and positions; `slots` is every
    row's whole table (8 pages: 8 blocks of 1, or 3 blocks of 3)."""
    eng = ServingEngine(TINY["dense"].replace(attn_pages_per_block=ppb),
                        params, max_batch=4, max_seq=64, page_size=8,
                        prefill_chunk=16)
    last = {"prefill": [], "decode": []}
    pf, df = eng.prefill_fn, eng.decode_fn

    def prefill(params, chunk, arena, bt, start, clen, st):
        last["prefill"].append([s + n - 1 if n else -1
                                for s, n in zip(start.tolist(),
                                                clen.tolist())])
        return pf(params, chunk, arena, bt, start, clen, st)

    def decode(params, arena, bt, positions, tokens, st):
        last["decode"].append(positions.tolist())  # inert rows: 0
        return df(params, arena, bt, positions, tokens, st)

    eng.prefill_fn, eng.decode_fn = prefill, decode
    _submit(eng, 4, plen=37, max_new=5)
    eng.run()
    nb = -(-8 // ppb)

    def by_hand(lasts):
        return sum(j * ppb * 8 <= p for p in lasts for j in range(nb))

    spans = sorted(_mine(eng), key=lambda s: s.start_ns)
    for kind in ("prefill", "decode"):
        got = [(s.attrs["blocks"], s.attrs["slots"]) for s in spans
               if s.name == f"engine.{kind}.dispatch"]
        assert got == [(by_hand(p), 4 * nb) for p in last[kind]]
        assert any(0 < b < n for b, n in got)      # the walk is cut short


def test_verify_dispatch_spans_count_each_window_to_its_last_candidate(
        params):
    """A speculative window's verify walk: each live row up to the block
    holding its last candidate, start + k; the other rows walk none."""
    k = 2
    eng = _engine(params, speculate_k=k, draft="self:1")
    ends = []
    fused = eng.fused_fn

    def spy(p, dp, cache, last, st, arena, bt, start, mask):
        ends.append([s + k if m else -1
                     for s, m in zip(start.tolist(), mask.tolist())])
        return fused(p, dp, cache, last, st, arena, bt, start, mask)

    eng.fused_fn = spy
    _submit(eng, 3, plen=20, max_new=10)
    eng.run()
    got = [(s.attrs["blocks"], s.attrs["slots"])
           for s in sorted(_mine(eng), key=lambda s: s.start_ns)
           if s.name == "engine.verify.dispatch"]
    assert got and got == [(sum(e // 8 + 1 for e in row if e >= 0), 4 * 8)
                           for row in ends]


def test_contiguous_layout_dispatches_count_their_rows():
    cfg = TINY["ssm"]
    eng = ServingEngine(cfg, registry.get_family(cfg).init(jax.random.key(0),
                                                           cfg),
                        max_batch=4, max_seq=64)
    assert eng.layout != "paged"
    rng = np.random.default_rng(3)
    for u in range(3):
        eng.submit(Request(uid=u, max_new_tokens=4, prompt=rng.integers(
            0, cfg.vocab_size, 12).astype(np.int32)))
    eng.run()
    spans = _mine(eng)
    pre = [s.attrs for s in spans if s.name == "engine.prefill.dispatch"]
    dec = [s.attrs for s in spans if s.name == "engine.decode.dispatch"]
    assert pre == [{"rows": 1, "batch": 1}] * 3
    assert dec and all(a["batch"] == 4 and 1 <= a["rows"] <= 3 for a in dec)
    up = {s.id: s.name for s in spans}
    # the whole prompt is prefilled at its admission
    assert {up[s.parent] for s in spans
            if s.name == "engine.prefill.dispatch"} == {"engine.admit"}
    assert {up[s.parent] for s in spans
            if s.name == "engine.decode.dispatch"} == {"engine.step"}


def test_ring_stays_at_its_bound():
    for i in range(tracing.RING + 100):
        tracing.record("test.fill", i, i + 1)
    spans = tracing.spans()
    assert len(spans) == tracing.RING
    assert spans[-1].start_ns == tracing.RING + 99
    assert all(s.name == "test.fill" for s in spans[:100])


def test_engine_steps_lie_inside_bench_steps_under_the_profiler(params,
                                                                tmp_path):
    eng = _engine(params)
    _submit(eng, 2, max_new=3)
    jax.profiler.start_trace(str(tmp_path))
    try:
        while eng.pending or eng.slots:
            with jax.profiler.TraceAnnotation("bench.step"):
                eng.step()
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    host = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host") for ln in p.lines
            for e in ln.events if e.name in ("bench.step", "engine.step")]
    bench = sorted((s, e) for n, s, e in host if n == "bench.step")
    steps = sorted((s, e) for n, s, e in host if n == "engine.step")
    assert len(steps) == len(bench) == eng.steps
    for (bs, be), (s, e) in zip(bench, steps):
        assert bs <= s <= e <= be
