"""Closed-loop traffic: `clients` callers, each sending its next request
as soon as its previous one finishes (no think time).

Parameters (the mix's JSON file): `clients`, `per_client` (requests
queued per client, more than a window can finish), `stagger_s` (first
submissions spread over this many seconds), and the `prompt` and
`output` length distributions (see harness/dists.py). The callers'
sequences of lengths are the mix's own, the same for every seed: the
pooled lengths are laid out by a fixed generator so that each round
(every client's j-th request) holds one length from each band of ranks.
A seed orders those sequences over the staggered first submissions and
draws the token ids. So every seed sends the same work and the window
sees about the same share of it; an order drawn anew per seed changed
the output tokens inside a 51 s window by up to 8 % from seed to seed
on a TPU v5e."""
from __future__ import annotations

from harness.dists import quantiles, rng_streams, stratified

LAYOUT_SEED = 0


class ClosedLoop:
    def __init__(self, spec: dict, seed: int, seconds: float, vocab: int):
        c, k = spec["clients"], spec["per_client"]
        n = c * k
        r_order, r_tok = rng_streams(seed, 2)
        # round j (every client's j-th request) holds one length from
        # each of `c` bands of ranks, in a layout fixed by the mix
        lay_in, lay_out = rng_streams(LAYOUT_SEED, 2)
        plen = stratified(quantiles(spec["prompt"], n), c, lay_in)
        olen = stratified(quantiles(spec["output"], n), c, lay_out)
        # client ci sends sequence seq[ci] and first submits at slot ci
        seq = r_order.permutation(c)
        self.requests = []
        self._queues = []
        for ci in range(c):
            q = []
            for j in range(k):
                uid, at = ci * k + j, j * c + seq[ci]
                r = dict(uid=uid, client=ci, due=None,
                         max_new=int(olen[at]),
                         prompt=r_tok.integers(0, vocab, int(plen[at]),
                                               dtype="int32"))
                self.requests.append(r)
                q.append(r)
            q[0]["due"] = (ci + 0.5) / c * spec["stagger_s"]
            self._queues.append(q)
        self._ready = sorted((q[0] for q in self._queues),
                             key=lambda r: r["due"])
        self._client_of = {r["uid"]: r["client"] for r in self.requests}

    def pop_due(self, now: float) -> list[dict]:
        out = [r for r in self._ready if r["due"] <= now]
        self._ready = [r for r in self._ready if r["due"] > now]
        return out

    def next_due(self) -> float | None:
        return min((r["due"] for r in self._ready), default=None)

    def finished(self, uid: int, now: float) -> None:
        """The client whose request `uid` finished sends its next one,
        due now."""
        q = self._queues[self._client_of[uid]]
        q.pop(0)
        if q:
            q[0]["due"] = now
            self._ready.append(q[0])


def make(spec: dict, seed: int, seconds: float, vocab: int) -> ClosedLoop:
    return ClosedLoop(spec, seed, seconds, vocab)
