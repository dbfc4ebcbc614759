"""On-chip serving benchmark: one cell, one run, one JSON line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Everything about the cell is found by name from BENCHMARK.json (see
harness/spec.py). The run draws the weights and the traffic from the
seed, warms up the cell's own programs, serves the traffic through
`ServingEngine` for `--seconds` of host clock (then waits, at most a
minute, for the first token of every request due in the window), and
checks a sample of what it served, finished or in flight, against the
float32 reference of the configuration's block. With `--trace 1` it
also records a profiler trace of the window and reports the per-layer
metrics instead of the end-to-end ones. The last line of standard output is
the result; the last lines of standard error are the numbers compared,
each beside its limit. Without an accelerator it exits non-zero and
prints no result."""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                                # noqa: E402
import gc                                                      # noqa: E402
import json                                                    # noqa: E402
import sys                                                     # noqa: E402
from pathlib import Path                                       # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from harness import spec as S                                  # noqa: E402


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


class NoDevice(SystemExit):
    pass


def device_info(chips: int, require_tpu: bool = True):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def pick_sample(data: dict, n: int, seed: int) -> list[int]:
    """Requests to check, finished or still in flight: the longest
    (prompt + tokens served so far) and others drawn from the seed."""
    import numpy as np
    served = sorted(u for u, ts in data["tokens"].items() if ts)
    if not served:
        return []
    size = {u: len(data["requests"][u]["prompt"]) + len(data["tokens"][u])
            for u in served}
    longest = max(served, key=lambda u: (size[u], u))
    rest = [u for u in served if u != longest]
    rng = np.random.default_rng(np.random.SeedSequence(seed).spawn(5)[4])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def token_count_errors(data: dict, vocab: int) -> list[int]:
    """Requests whose tokens break what was asked: a finished one with
    another count than its `max_new`, any with more, or a token outside
    the vocabulary."""
    bad = []
    for u, ts in data["tokens"].items():
        want = data["requests"][u]["max_new"]
        if ((u in data["finished"] and len(ts) != want) or len(ts) > want
                or not all(0 <= t < vocab for t in ts)):
            bad.append(u)
    return bad


def judge(gaps: list[float], bad: list[int], limit: float):
    """The comparison that decides `correct`, from the widest gap of
    each checked request; returns (ok, the numbers beside their limits)."""
    worst = max(gaps, default=None)
    checks = {
        "served_logit_gap": {"value": worst, "limit": limit},
        "checked_requests": {"value": len(gaps), "limit": 1},
        "token_count_errors": {"value": len(bad), "limit": 0},
    }
    ok = len(gaps) >= 1 and not bad and worst <= limit
    return ok, checks


def check_served(params, D, data, parts, seed, *, control=False):
    """Compares a seeded sample of what the window served with the
    float32 reference; returns (ok, checks, per-request readings). With
    `control`, the fp8 control's readings at the same positions go
    through the same `judge`: `control_correct` has to come out false."""
    eng, chk = parts["engine"], parts["check"]
    sample = pick_sample(data, chk["sample"], seed)
    bad = token_count_errors(data, D["V"])
    pairs = [(data["requests"][u]["prompt"], data["tokens"][u])
             for u in sample]
    gaps = parts["block"].served_gaps(
        params, D, pairs, max_seq=eng["max_seq"],
        max_new=parts["traffic"]["output"]["max"], control=control)
    served = [float(g.max()) for g, _ in gaps]
    ok, checks = judge(served, bad, chk["gap_limit"])
    readings = dict(sample=sample, served=served,
                    in_flight=sum(u not in data["finished"] for u in sample),
                    served_tokens=sum(len(p[1]) for p in pairs))
    if control:
        readings["control"] = [float(g8.max()) for _, g8 in gaps]
        readings["control_correct"], readings["control_checks"] = judge(
            readings["control"], bad, chk["gap_limit"])
    return ok, checks, readings


def end_to_end(data: dict, setup_s: float) -> tuple[dict, dict]:
    from harness.latency import gap_samples, percentile, ttft_samples, \
        window_rate
    close, t0 = data["close"], data["t0"]
    ttft = ttft_samples(data["due"], data["first"], close, data["end"])
    # a window in which no request got a token is one gap as long as it
    gaps = gap_samples(data["stamps"], data["finished"], close) \
        or [close - t0]
    return {
        "ttft_p90_ms": 1e3 * percentile(ttft, 90),
        "itl_p95_ms": 1e3 * percentile(gaps, 95),
        "output_tok_s": window_rate(data["stamps"], t0, close),
        "setup_s": setup_s,
    }, dict(ttft_samples=len(ttft), gap_samples=len(gaps))


def per_layer(ctx: dict, wanted: list[dict]) -> dict:
    out = {}
    for m in wanted:
        v = S.metric_reader(m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def serve_cell(parts: dict, seed: int, seconds: float, devs, *,
               trace: bool = False, fault=None) -> dict:
    """One run of a cell on `devs`; returns everything the result line
    and the checks need. `fault`, if given, is called with the engine
    before the window (the tests plant faults through it)."""
    from harness.model import check_layout
    from harness.serve import (CompileCounter, Recorder, build_engine,
                               drive, warm_up)
    from harness.trace import Capture

    cell, config, traffic = parts["cell"], parts["config"], parts["traffic"]
    eng = parts["engine"]
    chips = cell["chips"]
    mesh = sharding = None
    if chips > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        from repro.launch.mesh import MEM_AXIS
        mesh = Mesh(devs, (MEM_AXIS,))
        sharding = NamedSharding(mesh, PartitionSpec())
    B = parts["block"]
    cfg = B.model_config(config, eng)
    D = B.dims(config)
    t = time.perf_counter()
    params = B.make_weights(config, seed, sharding=sharding)
    check_layout(params, cfg)
    log(f"weights drawn in {time.perf_counter() - t:.1f}s")
    engine = build_engine(cfg, params, eng, mesh)
    t = time.perf_counter()
    shapes = warm_up(engine)
    log(f"warmed up {shapes} in {time.perf_counter() - t:.1f}s")
    source = parts["generator"].make(traffic, seed, seconds, D["V"])
    if fault is not None:
        fault(engine)
    rec = Recorder(engine)
    capture = Capture() if trace else None

    def window(opening: bool) -> None:
        rec.on = opening
        if capture:
            capture(opening)

    with CompileCounter() as counter:
        data = drive(engine, source, seconds, on_window=window)
    setup_s = data["t0"] - T_START
    peak = memory_peak(devs)
    stats = engine.stats()
    engine.arena.kv = None
    del engine
    gc.collect()
    out = dict(data=data, setup_s=setup_s, peak=peak, D=D, chips=chips,
               compiles=counter.count,
               record=dict(prefill=rec.prefill, decode=rec.decode),
               stats=stats)
    if trace:
        t = time.perf_counter()
        out["trace"] = capture.load(parts["patterns"])
        log(f"trace read in {time.perf_counter() - t:.1f}s")
    t = time.perf_counter()
    out["correct"], out["checks"], out["readings"] = check_served(
        params, D, data, parts, seed)
    log(f"reference check of {len(out['readings']['sample'])} requests "
        f"({out['readings']['in_flight']} in flight, "
        f"{out['readings']['served_tokens']} tokens) in "
        f"{time.perf_counter() - t:.1f}s")
    return out


def result_line(parts: dict, run: dict, devs, trace: bool) -> dict:
    from harness import trace as T
    data = run["data"]
    units = {m["name"]: m["unit"] for m in parts["end_to_end"]}
    e2e, counts = end_to_end(data, run["setup_s"])
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": run["peak"]}
    res = {"correct": bool(run["correct"]),
           "attempted": len(data["due"]), "failed": 0}
    close = data["close"]
    log(f"window {close - data['t0']:.3f}s, {data['ticks']} ticks, "
        f"{len(data['due'])} requests due, "
        f"{sum(t <= close for t in data['finished'].values())} finished, "
        f"waited {data['end'] - close:.3f}s past the close for "
        f"{sum(data['first'].get(u, close) > close for u in data['due'])} "
        f"first tokens, {counts['ttft_samples']} first-token samples, "
        f"{counts['gap_samples']} gaps, compiles in window "
        f"{run['compiles']}, generator late by up to "
        f"{1e3 * max(data['lateness'], default=0):.1f} ms, "
        f"preemptions {run['stats']['preemptions']}")
    log("end to end: " + ", ".join(f"{k} {v:.4f}" for k, v in e2e.items()))
    if not trace:
        res["metrics"] = {k: {"value": v, "unit": units[k]}
                          for k, v in e2e.items() if k in units}
    else:
        pat = parts["patterns"]
        tr = run["trace"]
        busy, window = T.busy_share(tr, pat["ops_line"])
        peaks = S.peaks_for(parts["peaks"], dev.device_kind)
        ctx = dict(trace=tr, patterns=pat, record=run["record"],
                   window_s=data["close"] - data["t0"], ticks=data["ticks"],
                   D=run["D"], peaks=peaks, chips=run["chips"],
                   block=parts["block"], kv_bytes=parts["block"].KV_BYTES)
        res["metrics"] = per_layer(ctx, parts["per_layer"])
        device["busy_s"] = busy
        device["window_s"] = window
        res["breakdown"] = {
            "device_ops": T.top_ops(tr, pat["ops_line"]),
            "idle_gaps": T.idle_gaps(tr, pat["ops_line"])}
    res["device"] = device
    res["checks"] = run["checks"]
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    parts = S.resolve(args.workload)
    import jax
    from repro.utils.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        devs = device_info(parts["cell"]["chips"])
        S.peaks_for(parts["peaks"], devs[0].device_kind)
    except (NoDevice, KeyError) as e:
        log(f"no result: {e}")
        return 3
    log(f"{args.workload}: {devs[0].device_kind} x{len(devs)}, seed "
        f"{args.seed}, {args.seconds}s, trace {args.trace}")
    run = serve_cell(parts, args.seed, args.seconds, devs,
                     trace=bool(args.trace))
    res = result_line(parts, run, devs, bool(args.trace))
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
