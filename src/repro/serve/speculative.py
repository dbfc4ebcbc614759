"""Speculative decode: a draft proposes k tokens, ONE paged walk verifies.

Decode is the bandwidth-bound phase — every plain decode step walks the
whole page arena to produce one token.  Speculation converts k of those
sequential walks into a single batched paged-prefill VERIFY call (the
ragged chunked-prefill machinery IS the verify step): a cheap draft
model proposes a k-token window per slot, the target writes all k+1
candidates into the slot's pages and judges them in one dispatch, and
in-step accept/reject emits the matched prefix plus one bonus token.

The determinism contract (serve/sampling.py) does the heavy lifting:
token t of a slot is a pure function of (target logits at t,
fold_in(key(seed), t)), so the verify step can COMPUTE the exact token
plain decode would emit at every window position and acceptance is
exact-match against it — the emitted stream is byte-identical to
non-speculative decode by construction, for greedy AND sampled rows
(`sampling.verify_tokens`).  The draft proposes with the SAME
counter-derived keys (Gumbel coupling), so agreement — hence the
accept rate — tracks how well draft logits approximate target logits.

Two draft shapes, one class:

* **truncated self-draft** (`"self:N"`) — the target's first N layers
  with shared embed/final-norm/head (`registry.self_draft_params`, zero
  extra weights).  Its contiguous KV cache can REWIND: rejected window
  positions are dropped by resetting `pos` (decode attention masks
  everything past it), no replay needed.
* **paired draft** (e.g. `"mamba2-130m"`, `registry.DRAFT_PAIRS`) — an
  independent small model.  Recurrent state cannot rewind, so rollback
  re-advances from the pre-propose checkpoint with a masked replay of
  the accepted tokens (checkpoints are free: jax pytrees are immutable,
  keeping the old reference IS the checkpoint).

The draft serves from its own CONTIGUOUS cache (it never touches the
page arena); the engine tracks per-slot `draft_pos` — how many context
tokens the draft has consumed — and `sync()` catches any row up with a
masked bucketed advance (admission, preempt/resume, fork, and slots
that decoded through the plain path while excluded from speculation).
"""
from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from repro.models import registry
from repro.models.config import ModelConfig
from repro.serve.kv_cache import batch_axis_index
from repro.serve.sampling import SamplingState, sample_tokens, verify_tokens

# widest single masked-advance dispatch during sync; longer catch-ups
# loop (bounds the per-width jit cache AND the compile time of the
# unrolled... scanned advance body)
SYNC_CHUNK = 128


def _bucket(n: int) -> int:
    """Power-of-two width bucket (static scan lengths, few compiles)."""
    r = 1
    while r < n:
        r *= 2
    return min(r, SYNC_CHUNK)


def _mask_rows(bi: int, mask, new, old):
    """Per-leaf row select: take `new`'s rows where mask (b,) holds,
    broadcasting the mask along the leaf's batch axis `bi`."""
    shape = [1] * new.ndim
    shape[bi] = mask.shape[0]
    return jnp.where(mask.reshape(shape), new, old)


class DraftModel:
    """The draft side of speculative decode, engine-slot addressed:
    batch row i of the draft cache mirrors engine slot i."""

    def __init__(self, cfg: ModelConfig, params, spec: str | None = None, *,
                 max_batch: int, max_seq: int, init_key=None):
        self.target_cfg = cfg
        self.spec = spec = spec or registry.default_draft(cfg)
        self.cfg = dcfg = registry.draft_config(cfg, spec)
        self.fam = fam = registry.get_family(dcfg)
        if registry.is_self_draft(cfg, dcfg):
            self.params = registry.self_draft_params(params, dcfg)
        else:
            self.params = fam.init(
                init_key if init_key is not None else jax.random.key(0), dcfg)
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.cache = fam.init_cache(dcfg, max_batch, max_seq)
        axes = fam.cache_axes()
        self._bi = {n: batch_axis_index(tuple(axes[n])) for n in self.cache}
        # KV caches rewind (pos masks the garbage tail); recurrent state
        # leaves (conv/ssm) must replay from a checkpoint instead
        self.rewindable = set(self.cache) <= {"k", "v", "pos"}
        self._ckpt = None
        self._jits: dict = {}
        self._clear = jax.jit(self._clear_impl)

    # ----------------------------------------------------- jitted bodies

    def _propose_fn(self, r: int):
        """r-step propose scan: consume [last, d_0..d_{r-2}], emit
        [d_0..d_{r-1}] drawn with each row's counter-derived key at
        emission indices step..step+r-1.  One dispatch per window.  The
        scan runs r = k+1 steps so that when the WHOLE window is
        accepted the draft has already consumed d_{k-1} and needs no
        catch-up (the r-th proposal is discarded, it only exists to
        advance the cache)."""
        key = ("propose", r)
        if key not in self._jits:
            fam, dcfg = self.fam, self.cfg

            def propose(params, cache, tokens, st: SamplingState):
                def body(carry, _):
                    cache, toks, st = carry
                    cache, logits = fam.decode_step(params, dcfg, cache, toks)
                    nxt = sample_tokens(logits, st)
                    st = st._replace(step=st.step + 1)
                    return (cache, nxt, st), nxt

                (cache, _, _), out = jax.lax.scan(
                    body, (cache, tokens, st), None, length=r)
                return cache, jnp.moveaxis(out, 0, 1)        # (b, r)

            self._jits[key] = jax.jit(propose)
        return self._jits[key]

    def _advance_fn(self, r: int):
        """Masked r-step advance: row i consumes tokens[i, :n[i]], rows
        with n[i] == 0 (and every step past n[i]) keep their old cache
        leaves — one scan serves ragged catch-up AND state-draft
        replay."""
        key = ("advance", r)
        if key not in self._jits:
            fam, dcfg, bi = self.fam, self.cfg, self._bi

            def advance(params, cache, tokens, n):
                def body(cache, xs):
                    toks, j = xs
                    new, _ = fam.decode_step(params, dcfg, cache, toks)
                    live = j < n                              # (b,)
                    return {name: _mask_rows(bi[name], live, new[name],
                                             cache[name])
                            for name in cache}, None

                cache, _ = jax.lax.scan(
                    body, cache,
                    (jnp.moveaxis(tokens, 0, 1), jnp.arange(r)))
                return cache

            self._jits[key] = jax.jit(advance)
        return self._jits[key]

    def fused_fn(self, k: int):
        """One-dispatch speculative window for REWINDABLE drafts: the
        propose scan, the target's ragged verify walk, accept/reject
        AND the draft-cache rewind fused into a single jitted call —
        half the dispatches and half the host round-trips of the
        propose-then-verify two-call path (the decode hot loop is
        dispatch-bound; the intermediate draft window never visits the
        host).

        The in-jit rewind is the optimistic `pos = pos0 + accept + 1`
        per live row: a row that emits FEWER tokens than accept+1 hit a
        stop token or its budget and retires this tick, so its draft
        row is dead state either way — no host-side correction path
        exists.  Rows outside `live` keep pos unchanged (their scan
        writes land past pos, masked by decode attention like any
        rewound tail).

        Returns None for state drafts (their rollback replays from a
        host-held checkpoint, which cannot live inside the jit) — the
        engine falls back to the two-call path there, as it does on
        sharded meshes (the sharded verify composes with shard_map)."""
        if not self.rewindable:
            return None
        key = ("fused", k)
        if key not in self._jits:
            fam, dcfg, tcfg = self.fam, self.cfg, self.target_cfg
            tfam = registry.get_family(tcfg)
            r = k + 1
            cpu = jax.default_backend() == "cpu"

            @partial(jax.jit, donate_argnums=() if cpu else (5,))
            @jax.named_scope("verify_step")
            def fused(tparams, dparams, cache, last, st: SamplingState,
                      arena, block_table, start, live):
                pos0 = cache["pos"]

                def body(carry, _):
                    cache, toks, s = carry
                    cache, logits = fam.decode_step(dparams, dcfg, cache,
                                                    toks)
                    nxt = sample_tokens(logits, s)
                    s = s._replace(step=s.step + 1)
                    return (cache, nxt, s), nxt

                (cache, _, _), out = jax.lax.scan(
                    body, (cache, last, st), None, length=r)
                window = jnp.moveaxis(out, 0, 1)             # (b, r)
                draft = window[:, :k]
                chunk = {"tokens": jnp.concatenate([last[:, None], draft],
                                                   axis=1)}
                clen = jnp.where(live, r, 0).astype(jnp.int32)
                arena, logits = tfam.paged_verify(tparams, tcfg, chunk,
                                                  arena, block_table,
                                                  start, clen)
                target, accept = verify_tokens(logits, draft, st)
                n = jnp.where(live, accept + 1, 0).astype(jnp.int32)
                cache = {**cache, "pos": pos0 + n}
                return arena, cache, target, accept

            self._jits[key] = fused
        return self._jits[key]

    def _clear_impl(self, cache, mask):
        return {name: _mask_rows(self._bi[name], mask,
                                 jnp.zeros_like(a), a)
                for name, a in cache.items()}

    # ------------------------------------------------------- engine API

    def sync(self, entries) -> None:
        """Catch rows up with their slots' context.  `entries` is a list
        of (row, suffix_tokens, reset): the row consumes `suffix_tokens`
        (np int32, the context tokens past its current draft_pos);
        `reset` zeroes the row first (fresh slot occupant / readmission
        — the row may hold a previous tenant's state)."""
        if not entries:
            return
        b = self.max_batch
        reset = np.zeros((b,), bool)
        for row, _, rst in entries:
            reset[row] = reset[row] or rst
        if reset.any():
            self.cache = self._clear(self.cache, reset)
        offset = 0
        remaining = max(len(t) for _, t, _ in entries)
        while offset < remaining:
            width = _bucket(remaining - offset)
            toks = np.zeros((b, width), np.int32)
            n = np.zeros((b,), np.int32)
            for row, t, _ in entries:
                part = t[offset:offset + width]
                toks[row, :len(part)] = part
                n[row] = len(part)
            self.cache = self._advance_fn(width)(
                self.params, self.cache, toks, n)
            offset += width

    def propose(self, last_tokens, st: SamplingState, k: int):
        """Propose a k-token window per row: last_tokens (b,) int32 (row
        i's newest emitted token — the draft's next input), st the
        slots' SamplingState with step = next emission index.  Returns
        draft (b, k) np.int32.  Checkpoints the cache for `rollback`."""
        self._ckpt = self.cache
        self.cache, window = self._propose_fn(k + 1)(
            self.params, self.cache, last_tokens, st)
        self._last = np.asarray(last_tokens)
        return np.asarray(window[:, :k])

    def rollback(self, target, n) -> None:
        """Land the verify outcome: row i's draft context grows by n[i]
        tokens (accepted + bonus; 0 for rows that sat the window out).
        target: (b, k+1) the verify step's exact target tokens; n: (b,)
        np int32.  Rewindable drafts keep the propose-written KV (the
        accepted prefix's inputs matched by construction) and reset
        `pos`; state drafts replay the accepted tokens from the
        checkpoint."""
        if self.rewindable:
            self.cache = {**self.cache, "pos": self._ckpt["pos"] + n}
        else:
            replay = np.concatenate([self._last[:, None],
                                     np.asarray(target)[:, :-1]], axis=1)
            self.cache = self._advance_fn(replay.shape[1])(
                self.params, self._ckpt, replay, n)
        self._ckpt = None

    def copy_row(self, src: int, dst: int) -> None:
        """fork(): the child slot adopts the parent's draft state."""
        out = {}
        for name, a in self.cache.items():
            idx = (slice(None),) * self._bi[name]
            out[name] = a.at[idx + (dst,)].set(a[idx + (src,)])
        self.cache = out

    def clear_row(self, row: int) -> None:
        """Drop a row's state (retirement/preemption hygiene — the next
        tenant resets anyway; this keeps debugging honest)."""
        mask = np.zeros((self.max_batch,), bool)
        mask[row] = True
        self.cache = self._clear(self.cache, mask)

    def stats(self) -> dict:
        return dict(spec=self.spec, family=self.cfg.family,
                    num_layers=self.cfg.num_layers,
                    rewindable=self.rewindable)
