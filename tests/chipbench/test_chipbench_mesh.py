"""The mesh layer's reader, `collective_exposed_ms`, on small synthetic
traces: an asynchronous pair counts its `-done` part only, a
synchronous collective counts whole, other chips and other operations
do not count, and a window of sharded steps whose trace holds no
collective fails the run."""
from __future__ import annotations

import pytest

import chipbench_common  # noqa: F401  (puts the harness on the path)
from harness import spec as S

PAT = S.load_json(S.BENCH_DIR / "patterns.json")
OPS = PAT["ops_line"]
READ = S.metric_reader("collective_exposed_ms").read


def _ctx(ops, chips=4, prefill=2, decode=3, other=None):
    devices = {"/device:TPU:0": {OPS: ops}}
    if other is not None:
        devices["/device:TPU:1"] = {OPS: other}
    return dict(trace={"devices": devices, "host": [["bench.step", 0, 1000]]},
                patterns=PAT, chips=chips,
                record={"prefill": [[(0, 64)]] * prefill,
                        "decode": [[5]] * decode})


START = "%all-gather-start.2 = (f32[2048,16]) all-gather-start(f32[2048,16] %m)"
DONE = "%all-gather-done.2 = f32[2048,4,16] all-gather-done(%all-gather-start.2)"
SYNC = ("%all-gather.12 = f32[2048,4,16]{0,2,1:T(8,128)S(1)} all-gather("
        "%all_gather_reshape.33), channel_id=1, replica_groups={{0,1,2,3}}")
REDUCE = "%all-reduce.3 = f32[8] all-reduce(f32[8] %x), to_apply=%add"
FUSION = ("%all_gather_reshape.33 = f32[2048,16] fusion(%a), kind=kLoop, "
          "calls=%fused_computation.7")
OTHER = "%fusion.1 = bf16[8,2048] fusion(%a)"


def test_async_pair_counts_its_done_part_only():
    ops = [[START, 100, 40], [OTHER, 110, 30], [DONE, 150, 10]]
    # 10 ns over 5 step calls
    assert READ(_ctx(ops)) == pytest.approx(10 / 1e6 / 5)


def test_synchronous_collectives_count_whole_on_chip_0_only():
    ops = [[SYNC, 200, 30], [FUSION, 230, 7], [REDUCE, 300, 5],
           [OTHER, 310, 50]]
    other = [[SYNC, 200, 400]]
    assert READ(_ctx(ops, other=other)) == pytest.approx(35 / 1e6 / 5)


def test_one_chip_or_no_step_calls_read_nothing():
    ops = [[SYNC, 200, 30]]
    assert READ(_ctx(ops, chips=1)) is None
    assert READ(_ctx(ops, prefill=0, decode=0)) is None


def test_sharded_steps_without_a_matching_collective_fail():
    with pytest.raises(RuntimeError, match="sharded step calls"):
        READ(_ctx([[OTHER, 100, 10], [START, 110, 5], [FUSION, 120, 5]]))
