"""The timed path: `ServingEngine` driven from one thread.

Each turn of the loop submits every request that is due, ticks the
engine once, and stamps every TokenEvent and FinishEvent on the host
clock as it leaves `events()`. The engine's two jitted steps are
wrapped so that each call records the lengths it served (for the
operation and byte counts) and opens a host span in the profiler's
trace."""
from __future__ import annotations

import time
from collections import defaultdict

import numpy as np
import jax


class Recorder:
    """Wraps `engine.prefill_fn` and `engine.decode_fn`: each call
    records (start, chunk_len) of the rows it advanced, or the position
    of each row it decoded."""

    def __init__(self, engine):
        self.prefill: list[list[tuple[int, int]]] = []
        self.decode: list[list[int]] = []
        self.on = True
        null = engine.arena.null_page
        pf, df = engine.prefill_fn, engine.decode_fn
        ann = jax.profiler.TraceAnnotation

        def prefill(params, chunk, arena, bt, start, clen, sampling):
            if self.on:
                m = np.asarray(clen) > 0
                self.prefill.append(list(zip(np.asarray(start)[m].tolist(),
                                             np.asarray(clen)[m].tolist())))
            with ann("bench.prefill_call"):
                return pf(params, chunk, arena, bt, start, clen, sampling)

        def decode(params, arena, bt, positions, tokens, sampling):
            if self.on:
                m = np.asarray(bt)[:, 0] != null
                self.decode.append(np.asarray(positions)[m].tolist())
            with ann("bench.decode_call"):
                return df(params, arena, bt, positions, tokens, sampling)

        engine.prefill_fn, engine.decode_fn = prefill, decode


def build_engine(cfg, params, engine_spec: dict, mesh=None):
    from repro.serve import ServingEngine
    return ServingEngine(cfg, params, max_batch=engine_spec["max_batch"],
                         max_seq=engine_spec["max_seq"],
                         page_size=engine_spec["page_size"],
                         pool_pages=engine_spec.get("pool_pages"), mesh=mesh)


def warm_up(engine) -> list[tuple[str, int]]:
    """Compile and run once every program the window will call: the
    prefill step at each chunk bucket and the decode step, all at
    `max_batch`, on inert rows (null block tables, chunk length 0)."""
    from repro.serve.sampling import greedy_state
    b = engine.max_batch
    bt = np.full((b, engine.max_pages), engine.arena.null_page, np.int32)
    z = np.zeros((b,), np.int32)
    st = greedy_state(b)
    shapes = []
    for c in engine.prefill_buckets:
        engine.arena.kv, out = engine.prefill_fn(
            engine.params, {"tokens": np.zeros((b, c), np.int32)},
            engine.arena.kv, bt, z, z, st)
        np.asarray(out)
        shapes.append(("prefill", c))
    engine.arena.kv, out = engine.decode_fn(engine.params, engine.arena.kv,
                                            bt, z, z, st)
    np.asarray(out)
    shapes.append(("decode", b))
    return shapes


class CompileCounter:
    """Counts JAX traces and backend compiles inside a `with` block."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.count = 0

    def _on(self, name, dur, **kw):
        if name in self.EVENTS:
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


DRAIN_S = 60.0    # the longest wait past the close for a first token


def drive(engine, source, seconds: float, on_window=None,
          drain_s: float = DRAIN_S) -> dict:
    """Serve `source` for `seconds` of host clock; returns the stamps.

    `on_window(open: bool)` is called right before the first submission
    and right after the last tick of the window (the traced run starts
    and stops the profiler there). After the close nothing more is
    submitted, but the engine ticks on until every request due in the
    window has its first token, for at most `drain_s`: a first token
    that comes late is late, and its wait counts (`end` is when the
    drain stopped). Tokens served in the drain are kept with their
    stamps; the window's metrics read only those up to `close`."""
    from repro.serve import Request, SamplingParams, TokenEvent, FinishEvent
    clock = time.perf_counter
    ann = jax.profiler.TraceAnnotation
    due, first, fin = {}, {}, {}
    stamps = defaultdict(list)
    tokens = defaultdict(list)
    lateness = []
    reqs = {}
    ticks = 0

    def tick(t0):
        with ann("bench.step"):
            engine.step()
        with ann("bench.drain"):
            t = clock()
            for ev in engine.events():
                if isinstance(ev, TokenEvent):
                    if ev.index != len(tokens[ev.uid]):
                        raise RuntimeError(
                            f"request {ev.uid}: token index {ev.index} "
                            f"after {len(tokens[ev.uid])} tokens")
                    tokens[ev.uid].append(ev.token)
                    stamps[ev.uid].append(t)
                    first.setdefault(ev.uid, t)
                elif isinstance(ev, FinishEvent):
                    fin[ev.uid] = t
                    source.finished(ev.uid, t - t0)

    if on_window:
        on_window(True)
    t0 = clock()
    end = t0 + seconds
    while True:
        now = clock()
        if now >= end:
            break
        with ann("bench.submit"):
            for r in source.pop_due(now - t0):
                u = r["uid"]
                due[u] = t0 + r["due"]
                lateness.append(now - due[u])
                reqs[u] = r
                engine.submit(Request(
                    uid=u, prompt=r["prompt"],
                    sampling=SamplingParams(max_new_tokens=r["max_new"])))
        if not (engine.pending or engine.slots):
            nxt = source.next_due()
            wake = end if nxt is None else min(end, t0 + nxt)
            time.sleep(max(0.0, wake - clock()))
            continue
        tick(t0)
        ticks += 1
    close = clock()
    if on_window:
        on_window(False)
    while (len(first) < len(due) and (engine.pending or engine.slots)
           and clock() < close + drain_s):
        tick(t0)
    return dict(t0=t0, close=close, end=clock(), due=due, first=first,
                finished=fin, stamps=dict(stamps), tokens=dict(tokens),
                requests=reqs, ticks=ticks, lateness=lateness)
