"""Traffic generators: the seed fixes the requests, every seed sends the
same multiset of sizes, lengths stay inside their clip range, and the
open loop's arrivals are a Poisson process."""
from __future__ import annotations

import chipbench_common  # noqa: F401  (puts the harness on the path)

import json

import numpy as np
import pytest

from harness import dists, spec as S

MIXES = ["chat", "docqa", "longdoc"]


def _mix(name):
    return json.loads((S.BENCH_DIR / "traffic" / f"{name}.json").read_text())


def _make(name, seed, seconds=30.0, vocab=1000):
    tr = _mix(name)
    return S.generator(tr["kind"]).make(tr, seed, seconds, vocab)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    a, b = _make(mix, 2**40 + 3), _make(mix, 2**40 + 3)
    assert len(a.requests) == len(b.requests)
    for x, y in zip(a.requests, b.requests):
        assert x["due"] == y["due"] and x["max_new"] == y["max_new"]
        np.testing.assert_array_equal(x["prompt"], y["prompt"])


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_permute_one_multiset(mix):
    a, b = _make(mix, 1), _make(mix, 2**40 + 1)
    for key in ("max_new",):
        assert sorted(r[key] for r in a.requests) == \
            sorted(r[key] for r in b.requests)
    assert sorted(len(r["prompt"]) for r in a.requests) == \
        sorted(len(r["prompt"]) for r in b.requests)
    assert [len(r["prompt"]) for r in a.requests] != \
        [len(r["prompt"]) for r in b.requests]


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_clipped_and_fit_the_engine(mix):
    tr = _mix(mix)
    src = _make(mix, 7)
    plen = [len(r["prompt"]) for r in src.requests]
    olen = [r["max_new"] for r in src.requests]
    assert tr["prompt"]["min"] <= min(plen) and max(plen) <= tr["prompt"]["max"]
    assert tr["output"]["min"] <= min(olen) and max(olen) <= tr["output"]["max"]
    assert tr["output"]["max"] >= max(olen)
    for w in S.benchmark()["workloads"]:
        if w["traffic"] == mix:
            eng = S.resolve(w["name"])["engine"]
            assert tr["prompt"]["max"] + tr["output"]["max"] <= eng["max_seq"]


def test_lognormal_quantiles_clip_both_tails():
    spec = {"dist": "lognormal", "median": 100, "sigma": 3.0, "min": 50,
            "max": 200}
    q = dists.quantiles(spec, 101)
    assert q.min() == 50 and q.max() == 200 and q[50] == 100


def test_open_loop_sends_rate_times_window_inside_it():
    tr = _mix("chat")
    src = S.generator("open_loop").make(tr, 5, 40.0, 1000)
    due = [r["due"] for r in src.requests]
    assert len(due) == round(tr["rate_per_s"] * 40.0)
    assert all(0 <= d < 40.0 for d in due)
    assert due == sorted(due)
    assert len(src.pop_due(20.0)) == sum(d <= 20.0 for d in due)
    src.finished(0, 21.0)                      # open loop: schedules nothing
    assert src.next_due() == min(d for d in due if d > 20.0)


def test_open_loop_arrivals_are_poisson():
    """Gaps of one seed are independent exponentials (CV about 1), and
    the counts in 5 s bins vary as a Poisson count does (variance about
    the mean): no seed smooths the bursts away."""
    tr = dict(_mix("chat"), rate_per_s=2.0)
    cv, disp = [], []
    for seed in range(40):
        due = np.array([r["due"] for r in S.generator("open_loop").make(
            tr, 2**35 + seed, 200.0, 100).requests])
        gaps = np.diff(due)
        cv.append(gaps.std() / gaps.mean())
        counts = np.histogram(due, bins=40, range=(0, 200))[0]
        disp.append(counts.var() / counts.mean())
    assert 0.9 < np.mean(cv) < 1.1
    assert 0.8 < np.mean(disp) < 1.2


def test_closed_loop_sends_next_request_on_finish():
    tr = _mix("docqa")
    src = S.generator("closed_loop").make(tr, 9, 30.0, 1000)
    first = src.pop_due(tr["stagger_s"])
    assert len(first) == tr["clients"]
    assert len({r["client"] for r in first}) == tr["clients"]
    assert src.pop_due(1e9) == [] and src.next_due() is None
    src.finished(first[0]["uid"], 12.5)
    (nxt,) = src.pop_due(12.5)
    assert nxt["client"] == first[0]["client"] and nxt["due"] == 12.5


@pytest.mark.parametrize("n,block", [(53, 8), (256, 16), (5, 8)])
def test_stratified_runs_hold_one_value_per_band(n, block):
    vals = np.arange(n) * 3
    out = dists.stratified(vals, block, np.random.default_rng(n))
    assert sorted(out) == sorted(vals)
    bands = np.array_split(np.sort(vals), block)
    band_of = {int(v): i for i, b in enumerate(bands) for v in b}
    full = min(len(b) for b in bands)
    for i in range(full):
        run = out[i * block:(i + 1) * block]
        assert sorted(band_of[int(v)] for v in run) == list(range(block))


def test_closed_loop_rounds_cover_every_band():
    tr = _mix("docqa")
    src = _make("docqa", 11)
    c = tr["clients"]
    first = sorted(len(q[0]["prompt"]) for q in src._queues)
    bands = np.array_split(np.sort([len(r["prompt"]) for r in src.requests]), c)
    assert all(b[0] <= f <= b[-1] for f, b in zip(first, bands))


def test_closed_loop_seeds_send_the_same_caller_sequences():
    """Every seed sends the same callers' sequences of (prompt, reply)
    lengths, only over other first-submission slots."""
    def seqs(seed):
        src = _make("docqa", seed)
        return [[(len(r["prompt"]), r["max_new"]) for r in q]
                for q in src._queues]
    a, b = seqs(5), seqs(2**40 + 5)
    assert a != b and sorted(a) == sorted(b)
