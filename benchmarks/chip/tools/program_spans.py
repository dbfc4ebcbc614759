"""What the program's own spans say about a run, and what they cost.

    python3 benchmarks/chip/tools/program_spans.py --workload <name> \
        --seed <n> --seconds <s>
    python3 benchmarks/chip/tools/program_spans.py --span-cost

A traced run of the cell as `run.py --trace 1` makes it, printing
`run.py`'s result line and then one more JSON line: the device's idle
time put down to the innermost program span open over it
(`harness/program.py`), and each phase's host self time per tick.
`--span-cost` times one span with the profiler off and on."""
from __future__ import annotations

import argparse
import json
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import run as R                                                # noqa: E402
from harness import program as P                               # noqa: E402
from harness import spec as S                                  # noqa: E402
from harness import trace as T                                 # noqa: E402


def span_cost(n: int = 20_000, repeats: int = 5) -> dict:
    """Median host ns of one `with tracing.span(...)` around nothing,
    with the profiler off and on."""
    import jax
    from repro.serve import tracing

    def once() -> float:
        t = time.perf_counter_ns()
        for _ in range(n):
            with tracing.span("cost.probe", tick=0, rows=1):
                pass
        return (time.perf_counter_ns() - t) / n

    off = statistics.median(once() for _ in range(repeats))
    d = tempfile.mkdtemp(prefix="span-cost-")
    jax.profiler.start_trace(d)
    try:
        on = statistics.median(once() for _ in range(repeats))
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(d, ignore_errors=True)
    return {"span_ns_profiler_off": off, "span_ns_profiler_on": on}


def self_ms_per_tick(spans, ticks: int) -> dict:
    """Host milliseconds per tick spent in each span less its children."""
    own, kids = P.phases(spans)
    out: dict = {}
    for s in own:
        ns = (s.end_ns - s.start_ns) - sum(
            c.end_ns - c.start_ns for c in kids.get(s.id, ()))
        out[s.name] = out.get(s.name, 0.0) + ns / 1e6 / ticks
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--span-cost", action="store_true")
    args = ap.parse_args()
    if args.span_cost:
        print(json.dumps(span_cost()), flush=True)
        return 0
    parts = S.resolve(args.workload)
    import jax
    from repro.utils.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = R.device_info(parts["cell"]["chips"])
    run = R.serve_cell(parts, args.seed, args.seconds, devs, trace=True)
    print(json.dumps(R.result_line(parts, run, devs, True)), flush=True)
    ticks = run["data"]["ticks"]
    ctx = dict(trace=run["trace"], patterns=parts["patterns"], ticks=ticks)
    spans = P.window(ctx)
    lo, hi = T.span_of(run["trace"])
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "ticks": ticks, "window_s": (hi - lo) / 1e9,
                      "idle_s_by_phase": P.idle_by_phase(ctx, spans),
                      "host_self_ms_per_tick": self_ms_per_tick(spans,
                                                                ticks)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
