"""BENCHMARK.json against the benchmark's contract, the harness finding
a new mix, metric and configuration by name alone, and a run that
finds no accelerator, or no program, printing no result."""
from __future__ import annotations

import chipbench_common  # noqa: F401  (puts the harness on the path)

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from harness import spec as S

ROOT = S.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
B = S.benchmark()


def test_top_level_keys():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= B["run_seconds"] <= 51
    for p in B["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert (ROOT / p).is_dir()
    assert B["command"][1] == "benchmarks/chip/run.py"


def test_names_units_and_keys():
    names = set()
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("benchmarks/chip/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert (S.BENCH_DIR / "blocks" / f"{cfg['block']}.py").is_file()
        names.add(c["name"])
    cells = set()
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in names
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in cells
        cells.add((w["config"], w["traffic"]))
    assert sum(w["chips"] == 4 for w in B["workloads"]) <= max(
        1, len(B["workloads"]) // 2)
    metrics = B["end_to_end"] + B["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")


def test_end_to_end_bounds():
    e2e = {m["name"]: m for m in B["end_to_end"]}
    assert set(e2e) == {"ttft_p90_ms", "itl_p95_ms", "output_tok_s",
                        "setup_s"}
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_every_per_layer_metric_has_a_reader_and_cells():
    cells = {w["name"] for w in B["workloads"]}
    e2e = {m["name"] for m in B["end_to_end"]}
    for m in B["per_layer"]:
        assert (S.BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        assert callable(S.metric_reader(m["name"]).read)


@pytest.mark.parametrize("cell", [w["name"] for w in B["workloads"]])
def test_every_cell_resolves(cell):
    parts = S.resolve(cell)
    assert parts["engine"]["max_seq"] <= \
        parts["config"]["max_position_embeddings"]
    assert parts["per_layer"] and len(parts["end_to_end"]) >= 2


def test_mix_files_hold_only_the_mix():
    """What depends on the model (the engine's capacity, the correctness
    limit) sits in the cell's own file, so another model can reuse a mix."""
    for f in (S.BENCH_DIR / "traffic").glob("*.json"):
        mix = json.loads(f.read_text())
        assert not {"engine", "check"} & set(mix), f.name
    for w in B["workloads"]:
        own = json.loads((S.BENCH_DIR / "cells" / f"{w['name']}.json")
                         .read_text())
        assert {"engine", "check"} <= set(own)
        assert own["check"]["sample"] >= 1 and own["check"]["gap_limit"] > 0


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        S.peaks_for(S.load_json(S.BENCH_DIR / "peaks.json"), "TPU v9")


def _copy(tmp_path):
    shutil.copytree(S.BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_new_mix_metric_and_config_are_found_by_name(tmp_path):
    """A later change adds files and BENCHMARK.json entries, edits nothing."""
    bench = _copy(tmp_path)
    chip = tmp_path / "benchmarks" / "chip"
    mix = json.loads((chip / "traffic" / "chat.json").read_text())
    mix.update(rate_per_s=3.0, why="bursty chat, added as data alone")
    (chip / "traffic" / "chat-burst.json").write_text(json.dumps(mix))
    (chip / "metrics" / "queue_wait_ms.py").write_text(
        "def read(ctx):\n    return 1.5\n")
    cfg = json.loads((chip / "configs" / "internlm2-1.8b.json").read_text())
    (chip / "configs" / "other-model.json").write_text(json.dumps(cfg))
    own = (chip / "cells" / "internlm2-1.8b.chat.json").read_text()
    (chip / "cells" / "other-model.chat-burst.json").write_text(own)
    bench["configs"].append(dict(bench["configs"][0], name="other-model",
                                 file="benchmarks/chip/configs/other-model.json"))
    bench["workloads"].append({"name": "other-model.chat-burst",
                               "config": "other-model",
                               "traffic": "chat-burst", "chips": 1,
                               "why": "throwaway"})
    bench["per_layer"].append({"name": "queue_wait_ms", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "scheduler tick (serve/engine.py)",
                               "moves": "ttft_p90_ms",
                               "workloads": ["other-model.chat-burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    code = (
        "import sys; sys.path.insert(0, 'benchmarks/chip')\n"
        "from harness import spec as S\n"
        "p = S.resolve('other-model.chat-burst')\n"
        "src = p['generator'].make(p['traffic'], 5, 10.0, 100)\n"
        "names = [m['name'] for m in p['per_layer']]\n"
        "print(len(src.requests), p['config']['name'], "
        "S.metric_reader('queue_wait_ms').read({}), 'queue_wait_ms' in names)\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["30", "internlm2-1.8b", "1.5", "True"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "internlm2-1.8b.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_accelerator_no_result():
    out = _run(ROOT)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "no result" in out.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    _copy(tmp_path)
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    out = _run(tmp_path)
    assert out.returncode != 0 and out.stdout.strip() == ""
