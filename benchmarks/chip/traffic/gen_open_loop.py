"""Open-loop traffic: independent users arrive as a Poisson process,
whether or not earlier requests have finished.

Parameters (the mix's JSON file): `rate_per_s`, `prompt` and `output`
length distributions (see harness/dists.py). A run of `seconds` sends
n = round(rate * seconds) requests, all due inside the window: the
arrival times of a Poisson process at that rate, given its n arrivals
(independent uniform times, sorted). A seed draws the times, orders
the n quantiles of each length distribution, and draws the token ids."""
from __future__ import annotations

from harness.dists import poisson_arrivals, quantiles, rng_streams


class OpenLoop:
    def __init__(self, spec: dict, seed: int, seconds: float, vocab: int):
        n = max(1, round(spec["rate_per_s"] * seconds))
        r_due, r_in, r_out, r_tok = rng_streams(seed, 4)
        due = poisson_arrivals(n, seconds, r_due)
        plen = r_in.permutation(quantiles(spec["prompt"], n))
        olen = r_out.permutation(quantiles(spec["output"], n))
        self.requests = [
            dict(uid=i, due=float(due[i]), max_new=int(olen[i]),
                 prompt=r_tok.integers(0, vocab, int(plen[i]),
                                       dtype="int32"))
            for i in range(n)]
        self._next = 0

    def pop_due(self, now: float) -> list[dict]:
        """Requests due at or before `now` (seconds from window start)."""
        out = []
        while (self._next < len(self.requests)
               and self.requests[self._next]["due"] <= now):
            out.append(self.requests[self._next])
            self._next += 1
        return out

    def next_due(self) -> float | None:
        if self._next < len(self.requests):
            return self.requests[self._next]["due"]
        return None

    def finished(self, uid: int, now: float) -> None:
        """Open loop: a finish schedules nothing."""


def make(spec: dict, seed: int, seconds: float, vocab: int) -> OpenLoop:
    return OpenLoop(spec, seed, seconds, vocab)
