"""Find a cell's parts by name.

`BENCHMARK.json` (at the root of the checkout) names each cell's
configuration and traffic mix; the parts live in files of their own:

    configs/<config>.json     the model's sizes and where they come from;
                              its "block" names
    blocks/<block>.py         the architecture: the configuration mapped
                              onto the program's, the weights drawn from
                              the seed, the float32 reference and its fp8
                              control, the operation and byte counts
    traffic/<mix>.json        the mix's parameters; its "kind" names
    traffic/gen_<kind>.py     the generator that reads them
    cells/<workload>.json     what belongs to the pair: the engine's
                              capacity and the correctness limit
    metrics/<metric>.py       one reader per per-layer metric
    peaks.json                the chips' peaks, keyed by device_kind
    patterns.json             trace names of the programs and kernels

Nothing here imports JAX or the program; a block does, once loaded."""
from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no file {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generator(kind: str):
    return _load_module(BENCH_DIR / "traffic" / f"gen_{kind}.py",
                        f"gen_{kind}")


@functools.cache
def block(name: str):
    """The block module `blocks/<name>.py`, loaded once a process (its
    jitted reference keeps its compiled programs)."""
    return _load_module(BENCH_DIR / "blocks" / f"{name}.py",
                        f"block_{name.replace('.', '_').replace('-', '_')}")


def metric_reader(name: str):
    return _load_module(BENCH_DIR / "metrics" / f"{name}.py",
                        f"metric_{name.replace('.', '_').replace('-', '_')}")


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(workload: str, bench: dict | None = None) -> dict:
    """Everything a run of `workload` needs, read from the files."""
    bench = bench or benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have: {', '.join(sorted(cells))})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[cell["config"]]
    config = load_json(ROOT / cfg_entry["file"])
    if "block" not in config:
        raise KeyError(f"{cfg_entry['file']} names no block")
    traffic = load_json(BENCH_DIR / "traffic" / f"{cell['traffic']}.json")
    own = load_json(BENCH_DIR / "cells" / f"{workload}.json")
    return dict(
        cell=cell, config=config, traffic=traffic,
        engine=own["engine"], check=own["check"],
        block=block(config["block"]),
        generator=generator(traffic["kind"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        peaks=load_json(BENCH_DIR / "peaks.json"),
        patterns=load_json(BENCH_DIR / "patterns.json"))


def peaks_for(peaks: dict, device_kind: str) -> dict:
    """The peaks of `device_kind`; a device not in the table is an
    error, not a default."""
    try:
        return peaks["devices"][device_kind]
    except KeyError:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(have: {', '.join(peaks['devices'])})") from None
