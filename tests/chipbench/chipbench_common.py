"""Shared set-up for the on-chip benchmark's CPU tests: the harness
under benchmarks/chip on the import path, and a tiny cell built from
the real configuration and traffic files with small widths. (Not a
`conftest.py`: the repo's other tests import theirs by that name.)"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

TINY_WIDTHS = dict(hidden_size=64, intermediate_size=128,
                   num_hidden_layers=2, num_attention_heads=4,
                   num_key_value_heads=2, head_dim=16, vocab_size=256)
TINY_TRAFFIC = dict(
    rate_per_s=20,
    prompt={"dist": "lognormal", "median": 24, "sigma": 0.8, "min": 4,
            "max": 64},
    output={"dist": "lognormal", "median": 8, "sigma": 0.7, "min": 2,
            "max": 24})
TINY_CELL = dict(
    engine={"max_batch": 4, "max_seq": 96, "page_size": 8,
            "pool_pages": 48},
    check={"sample": 3, "gap_limit": 0.25})


def load_run():
    """benchmarks/chip/run.py as a module of its own name."""
    spec = importlib.util.spec_from_file_location("chipbench_run",
                                                  BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_parts(widths=None, traffic=None, config="internlm2-1.8b",
               mix="chat", cell=None):
    """A cell's parts as `spec.resolve` gives them, at tiny sizes."""
    from harness import spec as S
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    cfg.update(widths or TINY_WIDTHS)
    tr = json.loads((BENCH / "traffic" / f"{mix}.json").read_text())
    tr.update(traffic or TINY_TRAFFIC)
    bench = S.benchmark()
    own = dict(TINY_CELL, **(cell or {}))
    return dict(cell={"name": "tiny", "chips": 1}, config=cfg, traffic=tr,
                engine=own["engine"], check=own["check"],
                block=S.block(cfg["block"]),
                generator=S.generator(tr["kind"]),
                end_to_end=bench["end_to_end"], per_layer=bench["per_layer"],
                peaks=S.load_json(BENCH / "peaks.json"),
                patterns=S.load_json(BENCH / "patterns.json"))


@pytest.fixture(scope="module")
def run_mod():
    return load_run()
