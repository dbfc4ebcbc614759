"""Find the knee of an open-loop cell once (not run by the benchmark).

    python3 benchmarks/chip/tools/sweep.py --workload <name> \
        --rates 0.8 1.0 1.2 ... --seconds <s> --seed <n>

In one process, serves the cell's traffic at each offered rate for a
window of `--seconds` and prints, per rate, the completed output
tokens per second, the first-token latency quantiles, and the backlog
of unadmitted requests sampled every tick: its mean over the first and
the last third of the window. The knee is the highest rate at which
the backlog does not grow across the window; the window has to be
longer than a request lives, so that the engine reaches its steady
state (the benchmark's own window is shorter)."""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import run as R                                                # noqa: E402
from harness import spec as S                                  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    parts = S.resolve(args.workload)
    import jax
    import numpy as np
    from repro.utils.compile_cache import use_compile_cache
    from harness.latency import percentile, ttft_samples, window_rate
    from harness.serve import build_engine, drive, warm_up
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = R.device_info(parts["cell"]["chips"])
    B = parts["block"]
    cfg = B.model_config(parts["config"], parts["engine"])
    D = B.dims(parts["config"])
    params = B.make_weights(parts["config"], args.seed)
    fns = None
    for rate in args.rates:
        engine = build_engine(cfg, params, parts["engine"])
        if fns is None:
            warm_up(engine)
            fns = (engine.prefill_fn, engine.decode_fn)
        backlog = []
        step = engine.step

        def ticked(engine=engine, step=step):
            step()
            backlog.append(len(engine.pending))

        engine.prefill_fn, engine.decode_fn = fns
        engine.step = ticked
        traffic = dict(parts["traffic"], rate_per_s=rate)
        src = parts["generator"].make(traffic, args.seed, args.seconds,
                                      D["V"])
        data = drive(engine, src, args.seconds, drain_s=0)
        third = max(1, len(backlog) // 3)
        half = (data["t0"] + data["close"]) / 2
        ttft = ttft_samples(data["due"], data["first"], data["close"])
        print(json.dumps(dict(
            rate=rate, requests=len(data["due"]),
            finished=len(data["finished"]), ticks=data["ticks"],
            output_tok_s=window_rate(data["stamps"], data["t0"],
                                     data["close"]),
            ttft_p50_ms=1e3 * percentile(ttft, 50),
            ttft_p90_ms=1e3 * percentile(ttft, 90),
            backlog_first_third=float(np.mean(backlog[:third])),
            backlog_last_third=float(np.mean(backlog[-third:])),
            backlog_end=backlog[-1] if backlog else 0,
            finished_last_half_per_s=sum(
                t > half for t in data["finished"].values())
            / (data["close"] - half),
            output_tok_s_last_half=window_rate(data["stamps"], half,
                                               data["close"]),
            slots_end=len(engine.slots),
            tick_ms=1e3 * (data["close"] - data["t0"]) / max(1, data["ticks"]),
            preemptions=engine.preemptions)), flush=True)
        engine.arena.kv = None
        del engine
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
