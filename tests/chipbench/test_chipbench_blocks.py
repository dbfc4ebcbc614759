"""Blocks: the architecture a configuration names, found by name.

The Llama block gives the values recorded from the harness before it
had a file of its own, and a stub block with nothing of Llama in it,
dropped into a copy of the benchmark beside a configuration that names
it, runs the comparison that decides `correct` and the count readers
with no file of `harness/` changed."""
from __future__ import annotations

import hashlib
import json
import shutil
import subprocess
import sys

import numpy as np
import jax
import pytest

from chipbench_common import ROOT, tiny_parts
from harness import spec as S

FIXTURES = ROOT / "tests" / "chipbench" / "fixtures"


def test_llama_block_gives_the_values_recorded_before_the_move():
    rec = json.loads((FIXTURES / "llama_block_values.json").read_text())
    parts = tiny_parts()
    B = parts["block"]
    params = B.make_weights(parts["config"], 12345)
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        h.update(np.asarray(leaf).view(np.uint16).tobytes())
    assert h.hexdigest() == rec["weights_sha256"]
    rng = np.random.default_rng(7)
    pairs = [(rng.integers(0, 256, n).tolist(), rng.integers(0, 256, k).tolist())
             for n, k in [(5, 3), (40, 24), (70, 10)]]
    gaps = B.served_gaps(params, B.dims(parts["config"]), pairs, max_seq=96,
                         max_new=24, control=True)
    # float32 sums on the CPU; the same program gives the same digits
    for (g, g8), want, want8 in zip(gaps, rec["gaps"], rec["control_gaps"]):
        np.testing.assert_allclose(g, want, rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(g8, want8, rtol=1e-6, atol=1e-7)


# (matmul params, model FLOPs, decode and prefill kernel (FLOPs, bytes),
# KV bytes a token) of two prefill rows and two decode rows, recorded
# from the harness before the move
RECORDED = {
    "internlm2-1.8b": (1699479552, 444335456256.0, (394592256.0, 197689344.0),
                       (2076180480.0, 47579136.0), 98304),
    "yi-9b-l24": (4414504960, 1152712835072.0, (789184512.0, 99434496.0),
                  (4152360960.0, 61538304.0), 49152),
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_llama_block_counts_at_published_widths(name):
    cfg = json.loads((S.BENCH_DIR / "configs" / f"{name}.json").read_text())
    assert cfg["block"] == "llama"
    B = S.block(cfg["block"])
    d = B.dims(cfg)
    rows, pos = [(0, 64), (100, 64)], [5, 2000]
    assert (B.matmul_params(d), B.model_flops(d, rows, pos),
            B.decode_kernel_cost(d, pos, B.KV_BYTES),
            B.prefill_kernel_cost(d, rows, B.KV_BYTES),
            B.kv_token_bytes(d)) == RECORDED[name]


STUB_RUN = r'''
import json, sys
sys.path.insert(0, "benchmarks/chip")
import run as R
from harness import spec as S
p = S.resolve("stub-model.chat")
B = p["block"]
params = B.make_weights(p["config"], 3)
D = B.dims(p["config"])
table = params["table"]

def greedy(prompt, k):
    out = []
    for _ in range(k):
        out.append(int(table[([prompt[-1]] + out)[-1]].argmax()))
    return out

prompts = {0: [1, 2, 3], 1: [7, 9], 2: [4]}
data = dict(tokens={0: greedy(prompts[0], 5), 1: greedy(prompts[1], 3),
                    2: greedy(prompts[2], 4)},
            requests={u: {"prompt": pr, "max_new": 5 if u == 0 else 6}
                      for u, pr in prompts.items()},
            finished={0: 1.0})
ok, _, rd = R.check_served(params, D, data, p, 3, control=True)
altered = dict(data, tokens=dict(data["tokens"]))
altered["tokens"][1] = [(t + 1) % D["V"] for t in data["tokens"][1]]
ok_altered = R.check_served(params, D, altered, p, 3)[0]
kernel = "%paged_decode_attention.3 = bf16[8] custom-call(), custom_call_target=\"tpu_custom_call\""
pkernel = "%paged_prefill_attention.8 = bf16[8] custom-call(), custom_call_target=\"tpu_custom_call\""
trace = {"devices": {"/device:TPU:0": {"XLA Ops": [[kernel, 100, 20],
                                                   [pkernel, 200, 100]]}},
         "host": [["bench.step", 90, 300]]}
ctx = dict(trace=trace, patterns=p["patterns"],
           record={"prefill": [[(0, 4)]], "decode": [[3, 5]]},
           window_s=1e-6, D=D, chips=1, block=B, kv_bytes=B.KV_BYTES,
           peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9})
read = {m: S.metric_reader(m).read(ctx)
        for m in ("mfu", "paged_decode_roofline", "paged_prefill_roofline")}
try:
    S.resolve("broken-model.chat")
    missing = None
except FileNotFoundError as e:
    missing = str(e)
print(json.dumps(dict(ok=ok, served=rd["served"], checked=len(rd["sample"]),
                      ok_altered=ok_altered, read=read, missing=missing)))
'''


def test_stub_block_runs_the_check_and_the_count_readers(tmp_path):
    """A later change adds a block, a configuration and a cell as new
    files and BENCHMARK.json entries; nothing in harness/ changes."""
    shutil.copytree(S.BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copy(FIXTURES / "block_stub.py", chip / "blocks" / "stub.py")
    bench = S.benchmark()
    for name, block in [("stub-model", "stub"), ("broken-model", "nonesuch")]:
        cfg = {"name": name, "source": "a bigram table", "block": block,
               "vocab_size": 32, "reduced": []}
        (chip / "configs" / f"{name}.json").write_text(json.dumps(cfg))
        (chip / "cells" / f"{name}.chat.json").write_text(json.dumps(
            {"engine": {"max_batch": 4, "max_seq": 64, "page_size": 8},
             "check": {"sample": 8, "gap_limit": 0.5}}))
        bench["configs"].append({"name": name, "source": cfg["source"],
                                 "file": f"benchmarks/chip/configs/{name}.json",
                                 "reduced": [], "why": "throwaway"})
        bench["workloads"].append({"name": f"{name}.chat", "config": name,
                                   "traffic": "chat", "chips": 1,
                                   "why": "throwaway"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    out = subprocess.run([sys.executable, "-c", STUB_RUN], cwd=tmp_path,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.splitlines()[-1])
    assert res["ok"] and res["checked"] == 3 and max(res["served"]) == 0.0
    assert not res["ok_altered"]
    assert res["read"]["mfu"] == pytest.approx(100 * 32 * 6 / (1e-6 * 1e12))
    assert res["read"]["paged_decode_roofline"] == pytest.approx(
        100 * 2e-9 / 20e-9)
    assert res["read"]["paged_prefill_roofline"] == pytest.approx(
        100 * 4e-9 / 100e-9)
    assert "benchmarks/chip/blocks/nonesuch.py" in res["missing"]


def test_a_configuration_that_names_no_block_is_an_error(tmp_path):
    bench = S.benchmark()
    cfg = json.loads((S.BENCH_DIR / "configs" / "internlm2-1.8b.json")
                     .read_text())
    del cfg["block"]
    path = tmp_path / "noblock.json"
    path.write_text(json.dumps(cfg))
    entry = next(c for c in bench["configs"] if c["name"] == "internlm2-1.8b")
    entry["file"] = str(path)
    with pytest.raises(KeyError, match="names no block"):
        S.resolve("internlm2-1.8b.chat", bench)
