"""The Llama block's model FLOPs and kernel operation and byte counts
against values worked out by hand for a tiny configuration."""
from __future__ import annotations

import chipbench_common  # noqa: F401  (puts the harness on the path)

import pytest

from harness import spec as S

C = S.block("llama")

# L=2, d=8, ff=16, hq=4, hkv=2, hd=2, V=10
D = dict(L=2, d=8, ff=16, hq=4, hkv=2, hd=2, V=10, theta=1e4, eps=1e-5)


def test_matmul_params():
    # per layer: wq 8x8 + wk, wv 8x4 each + wo 8x8 + 3 x 8x16 = 576;
    # two layers and an 8x10 head
    assert C.matmul_params(D) == 2 * 576 + 80


def test_model_flops_prefill_and_decode():
    p2 = 2 * (2 * 576 + 80)
    att = 4 * 2 * 4 * 2                      # 4 * L * hq * hd
    # a 3-token chunk from position 5 attends 6 + 7 + 8 keys; a decode
    # at position 9 attends 10
    want = p2 * 3 + att * (6 + 7 + 8) + p2 + att * 10
    assert C.model_flops(D, [(5, 3)], [9]) == want


def test_decode_kernel_cost():
    f, b = C.decode_kernel_cost(D, [0, 3], kv_bytes=2)
    # rows walk 1 and 4 positions; per layer 4*hq*hd flops per key,
    # K and V of each key (hkv*hd*2 bytes each), q and out (hq*hd*2 each)
    assert f == 2 * 4 * 4 * 2 * (1 + 4)
    assert b == 2 * (2 * 5 * 2 * 2 * 2 + 2 * (2 * 4 * 2 * 2))


def test_prefill_kernel_cost():
    f, b = C.prefill_kernel_cost(D, [(4, 2)], kv_bytes=2)
    assert f == 2 * 4 * 4 * 2 * (5 + 6)
    assert b == 2 * (2 * 6 * 2 * 2 * 2 + 2 * 2 * 4 * 2 * 2)


def test_counts_ignore_padding_and_inert_rows():
    assert C.model_flops(D, [], []) == 0
    assert C.decode_kernel_cost(D, [], 2) == (0, 0)


@pytest.mark.parametrize("name", ["internlm2-1.8b", "yi-9b-l24"])
def test_published_parameter_counts(name):
    import json
    cfg = json.loads((S.BENCH_DIR / "configs" / f"{name}.json").read_text())
    d = S.block(cfg["block"]).dims(cfg)
    total = C.matmul_params(d) + d["V"] * d["d"]          # plus embedding
    want = {"internlm2-1.8b": 1.89e9, "yi-9b-l24": 4.68e9}[name]
    assert total == pytest.approx(want, rel=0.01)
