"""Readings that set a cell's correctness limit (not run by the benchmark).

    python3 benchmarks/chip/tools/calibrate.py --workload <name> \
        --seeds 1 2 3 ... --seconds <s> [--control-seeds 1 2 3]

In one process, for each seed: draw the weights and the traffic, serve
the window through the timed path exactly as a run does, and compare
the sampled requests, finished or in flight, with the float32
reference (the program's reading). For each of `--control-seeds`, also
read the fp8 control at the same positions: the gap of the token that
the fp8 forward puts first, put through the same comparison as the
program's (`run.judge`) at the cell's limit, which has to find it not
correct. One JSON line per seed, then a summary line. The limit lies between the largest
program reading and the smallest control reading."""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import run as R                                                # noqa: E402
from harness import spec as S                                  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    parts = S.resolve(args.workload)
    import jax
    from repro.utils.compile_cache import use_compile_cache
    from harness.serve import build_engine, drive, warm_up
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = R.device_info(parts["cell"]["chips"])
    B = parts["block"]
    cfg = B.model_config(parts["config"], parts["engine"])
    D = B.dims(parts["config"])
    mesh = sharding = None
    if len(devs) > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        from repro.launch.mesh import MEM_AXIS
        mesh = Mesh(devs, (MEM_AXIS,))
        sharding = NamedSharding(mesh, PartitionSpec())
    fns = None
    program, control, control_correct = [], [], []
    for seed in args.seeds:
        t = time.perf_counter()
        params = B.make_weights(parts["config"], seed, sharding=sharding)
        engine = build_engine(cfg, params, parts["engine"], mesh)
        if fns is None:
            warm_up(engine)
            fns = (engine.prefill_fn, engine.decode_fn)
        engine.prefill_fn, engine.decode_fn = fns
        source = parts["generator"].make(parts["traffic"], seed,
                                         args.seconds, D["V"])
        data = drive(engine, source, args.seconds)
        engine.arena.kv = None
        del engine
        gc.collect()
        ctl = seed in args.control_seeds
        ok, checks, rd = R.check_served(params, D, data, parts, seed,
                                        control=ctl)
        program.append(max(rd["served"]))
        if ctl:
            control.append(max(rd["control"]))
            control_correct.append(rd["control_correct"])
        print(json.dumps(dict(seed=seed, correct=ok,
                              control_correct=rd.get("control_correct"),
                              finished=len(data["finished"]),
                              in_flight=rd["in_flight"],
                              checked=rd["sample"],
                              served_tokens=rd["served_tokens"],
                              program=rd["served"],
                              control=rd.get("control"),
                              seconds=time.perf_counter() - t)), flush=True)
        del params
        gc.collect()
    print(json.dumps(dict(workload=args.workload,
                          program_max=max(program), program=program,
                          control_min=min(control, default=None),
                          control=control, control_correct=control_correct,
                          limit=parts["check"]["gap_limit"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
