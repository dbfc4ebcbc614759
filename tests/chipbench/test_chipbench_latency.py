"""End-to-end arithmetic: latency from the due time, stragglers counted
at their age, every gap in the tail, and a window rate that a stall
lowers."""
from __future__ import annotations

import chipbench_common  # noqa: F401  (puts the harness on the path)

import numpy as np
import pytest

from harness.latency import gap_samples, percentile, ttft_samples, window_rate


@pytest.mark.parametrize("q", [0, 50, 90, 95, 100])
def test_percentile_matches_numpy(q):
    xs = np.random.default_rng(0).exponential(size=37)
    assert percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_first_token_latency_runs_from_due_time():
    # request 1 was due at 1.0 but submitted late: its wait still counts
    due = {0: 0.0, 1: 1.0}
    first = {0: 0.5, 1: 3.0}
    assert sorted(ttft_samples(due, first, close=10.0)) == [0.5, 2.0]


def test_request_without_first_token_counts_at_its_age():
    due = {0: 0.0, 1: 4.0, 2: 11.0}          # 2 is not due before close
    first = {0: 1.0}
    assert sorted(ttft_samples(due, first, close=10.0)) == [1.0, 6.0]


def test_every_gap_counts_not_a_mean_per_request():
    stamps = {0: [0.0, 0.1, 0.2, 1.2], 1: [0.0, 0.1]}
    gaps = gap_samples(stamps, finished={0: 1.2, 1: 0.1}, close=5.0)
    assert sorted(gaps) == pytest.approx([0.1, 0.1, 0.1, 1.0])
    assert percentile(gaps, 95) > 0.5        # the one long gap shows


def test_open_gap_of_unfinished_request_counts():
    stamps = {0: [0.0, 0.1]}
    assert sorted(gap_samples(stamps, finished={}, close=3.1)) == \
        pytest.approx([0.1, 3.0])


def test_stall_lowers_window_rate():
    steady = {0: list(np.arange(0, 10, 0.1))}
    stalled = {0: [t for t in np.arange(0, 10, 0.1) if not 4 <= t < 7]}
    assert window_rate(steady, 0.0, 10.0) == pytest.approx(10.0, rel=0.02)
    assert window_rate(stalled, 0.0, 10.0) < 0.75 * window_rate(
        steady, 0.0, 10.0)


def test_first_token_after_close_counts_at_its_real_time():
    # 1 got its first token in the wait past the close; 2 never did and
    # counts at its age when the wait stopped
    due = {0: 0.0, 1: 9.0, 2: 9.5}
    first = {0: 1.0, 1: 14.0}
    assert sorted(ttft_samples(due, first, close=10.0, end=20.0)) == \
        [1.0, 5.0, 10.5]


def test_gaps_read_only_tokens_up_to_close():
    stamps = {0: [0.0, 0.5, 1.0, 12.0], 1: [0.0, 0.25]}
    # 0 finished after the close: its open gap at the close counts
    gaps = gap_samples(stamps, finished={0: 12.0, 1: 0.25}, close=10.0)
    assert sorted(gaps) == pytest.approx([0.25, 0.5, 0.5, 9.0])
