"""Look at a trace before trusting its reduction.

    python3 benchmarks/chip/tools/record_trace.py --workload <name> \
        --seed <n> --seconds <s> [--out trace_out]

Runs one traced window of the cell, prints every plane and line of the
profiler's trace with its event count and its most frequent event
names, and writes a trimmed copy of the trace in the reduction's own
form (the first `--keep-ms` of the window, device and benchmark spans)
to <out>/trace_<workload>.json.gz, the form the tests read."""
from __future__ import annotations

import argparse
import collections
import gzip
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import run as R                                                # noqa: E402
from harness import spec as S                                  # noqa: E402
from harness import trace as T                                 # noqa: E402


class Inspect(T.Capture):
    keep_ms = 400.0
    out = None

    def load(self, patterns):
        import glob
        import os
        from jax.profiler import ProfileData
        f = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                      recursive=True)[0]
        pd = ProfileData.from_file(f)
        for plane in pd.planes:
            lines = list(plane.lines)
            R.log(f"PLANE {plane.name!r}: {len(lines)} lines")
            for ln in lines:
                evs = list(ln.events)
                names = collections.Counter(e.name for e in evs)
                t = [(e.start_ns, e.start_ns + e.duration_ns) for e in evs]
                span = (min(t)[0], max(e for _, e in t)) if t else None
                R.log(f"   LINE {ln.name!r}: {len(evs)} events, span "
                      f"{span}, top {names.most_common(6)}")
        tr = super().load(patterns)
        lo, _ = T.span_of(tr)
        hi = lo + self.keep_ms * 1e6
        trim = {"devices": {p: {ln: [e for e in evs if e[1] < hi]
                                for ln, evs in lines.items()}
                            for p, lines in tr["devices"].items()},
                "host": [e for e in tr["host"] if e[1] < hi]}
        self.out.mkdir(parents=True, exist_ok=True)
        with gzip.open(self.out / f"trace_{self.workload}.json.gz", "wt") as fh:
            json.dump(trim, fh)
        return tr


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--keep-ms", type=float, default=400.0)
    ap.add_argument("--out", default="trace_out")
    args = ap.parse_args()
    parts = S.resolve(args.workload)
    import jax
    from repro.utils.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = R.device_info(parts["cell"]["chips"])
    Inspect.keep_ms, Inspect.out = args.keep_ms, Path(args.out)
    Inspect.workload = args.workload
    T.Capture = Inspect
    run = R.serve_cell(parts, args.seed, args.seconds, devs, trace=True)
    print(json.dumps(R.result_line(parts, run, devs, True)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
