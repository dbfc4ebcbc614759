"""Jit-compiled serving steps: prefill, decode, in-step sampling.

`make_serve_fns(cfg)` returns jitted `prefill(params, batch, cache)` and
`decode(params, cache, tokens, sampling)` closures for any family with a
decode path.  Sampling executes INSIDE the jitted step against the
per-slot `SamplingState` (serve/sampling.py): greedy rows take the exact
argmax, sampled rows draw with a counter-derived threefry key — tokens,
never logits, cross the host boundary.  `decode_many` fuses N decode
steps into one `lax.scan` — one dispatch for a whole token budget (the
decode analogue of the paper's UCE sequencing a fixed schedule without
host round-trips).

`make_paged_serve_fns(cfg)` is the block-table-driven variant for
families with the paged-cache hooks: prefill consumes prompt CHUNKS
(advancing `start` offsets, so admission interleaves with decode) and
SAMPLES each row's next token at its last valid position (the first
generated token leaves the prefill step as a token too); decode walks
the UniMem arena through (b, max_pages) block tables — memory
proportional to tokens in flight, not slots x max_seq.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models import registry
from repro.serve.sampling import (SamplingState, greedy_state, sample_tokens,
                                  verify_tokens)


def sample_logits(logits, key, temperature: float):
    """logits: (b, V) -> tokens (b,).  Legacy single-temperature sampler
    kept for `decode_many` (a fixed-schedule tool, not the engine path —
    the engine samples per-request via `SamplingState`)."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits.astype(jnp.float32) / temperature
    return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)


def make_serve_fns(cfg: ModelConfig, *, temperature: float = 0.0):
    fam = registry.get_family(cfg)
    if fam.decode_step is None:
        raise ValueError(f"family {cfg.family!r} has no decode path")

    @jax.jit
    def prefill(params, batch, cache):
        cache, logits = fam.prefill(params, cfg, batch, cache)
        return cache, logits

    @jax.jit
    def decode(params, cache, tokens, sampling: SamplingState):
        cache, logits = fam.decode_step(params, cfg, cache, tokens)
        return cache, sample_tokens(logits, sampling)

    @partial(jax.jit, static_argnames=("num_steps",))
    def decode_many(params, cache, tokens, key, num_steps: int):
        """Scan `num_steps` decode steps; returns (cache, tokens (b, n))."""
        def body(carry, _):
            cache, toks, key = carry
            cache, logits = fam.decode_step(params, cfg, cache, toks)
            key, sub = jax.random.split(key)
            nxt = sample_logits(logits, sub, temperature)
            return (cache, nxt, key), nxt

        (cache, _, key), out = jax.lax.scan(
            body, (cache, tokens, key), None, length=num_steps)
        return cache, jnp.moveaxis(out, 0, 1), key

    return prefill, decode, decode_many


def make_paged_serve_fns(cfg: ModelConfig):
    """Jitted closures over the family's paged-cache hooks.

    prefill_chunk(params, chunk, arena, block_table, start (b,),
                  chunk_len (b,), sampling) -> (arena, next_tokens (b,))
        `chunk` is {"tokens": (b, c)[, "patches": (b, c, frontend_dim)]}
        — ONE bucketed width c serves every admitting row; chunk_len
        ragged-masks each row (0 = inert).  The returned tokens are
        sampled at each row's LAST VALID position — only the row whose
        prompt just completed consumes its token (emission counter 0).
    decode(params, arena, block_table, positions, tokens, sampling)
        -> (arena, next_tokens)

    Sampling is per-slot `SamplingState` arrays evaluated in-step; the
    (b, vocab) logits never leave the jit.  The bodies run under the
    name scopes `prefill_step` / `decode_step`; the programs keep the
    names `jit_prefill_chunk` / `jit_decode`, by which a profiler trace
    finds them.
    """
    fam = registry.get_family(cfg)
    if not registry.has_paged(cfg):
        raise ValueError(f"family {cfg.family!r} has no paged serving path")

    # The caller immediately replaces its arena with the returned one, so
    # donate it — XLA then scatters the new K/V pages in place instead of
    # copying the whole pool-sized arena every token step.  (CPU can't
    # donate and would warn per call.)
    cpu = jax.default_backend() == "cpu"

    @partial(jax.jit, donate_argnums=() if cpu else (2,))
    @jax.named_scope("prefill_step")
    def prefill_chunk(params, chunk, arena, block_table, start, chunk_len,
                      sampling: SamplingState):
        arena, logits = fam.paged_prefill(params, cfg, chunk, arena,
                                          block_table, start, chunk_len)
        return arena, sample_tokens(logits, sampling)

    @partial(jax.jit, donate_argnums=() if cpu else (1,))
    @jax.named_scope("decode_step")
    def decode(params, arena, block_table, positions, tokens,
               sampling: SamplingState):
        arena, logits = fam.paged_decode_step(params, cfg, arena,
                                              block_table, positions, tokens)
        return arena, sample_tokens(logits, sampling)

    return prefill_chunk, decode


def make_paged_verify_fn(cfg: ModelConfig):
    """Jitted speculative-verify step over the family's `paged_verify`
    hook — ONE ragged paged-prefill walk judges a whole k-token draft
    window per slot.

    verify(params, chunk, arena, block_table, start (b,), chunk_len (b,),
           draft (b, k), sampling) -> (arena, target (b, k+1), accept (b,))

    `chunk` is {"tokens": (b, k+1)} — row i's candidates
    [last_emitted, draft_0..draft_{k-1}] written at absolute positions
    start[i]..start[i]+k (chunk_len k+1 active, 0 inert like prefill).
    `target` holds the exact tokens plain decode would emit at emission
    indices sampling.step..sampling.step+k (greedy argmax or the
    counter-keyed threefry draw — serve/sampling.verify_tokens), and
    `accept` the matched draft prefix length; both leave the step as
    int32, logits never cross the host boundary."""
    fam = registry.get_family(cfg)
    if not registry.has_verify(cfg):
        raise ValueError(f"family {cfg.family!r} has no speculative-verify "
                         f"path")
    cpu = jax.default_backend() == "cpu"

    @partial(jax.jit, donate_argnums=() if cpu else (2,))
    @jax.named_scope("verify_step")
    def verify(params, chunk, arena, block_table, start, chunk_len, draft,
               sampling: SamplingState):
        arena, logits = fam.paged_verify(params, cfg, chunk, arena,
                                         block_table, start, chunk_len)
        target, accept = verify_tokens(logits, draft, sampling)
        return arena, target, accept

    return verify


def init_cache(cfg: ModelConfig, batch: int, max_seq: int):
    fam = registry.get_family(cfg)
    return fam.init_cache(cfg, batch, max_seq)


# one probe geometry shared by the HLO-structure tests and the
# serve_throughput --json gate (prefill_chunk != max_pages keeps the
# query tile shape from colliding with the decode-partials shape)
HLO_PROBE_GEOM = dict(max_batch=2, max_seq=64, page_size=8, prefill_chunk=4)


def bulk_attn_shapes(cfg: ModelConfig, *, max_batch: int, max_seq: int,
                     page_size: int, **_ignored) -> list[str]:
    """HLO result-type strings of the bulk attention buffers the fused
    paged kernels must never materialize: the gathered contiguous KV
    copy (its (b, mp, page, hkv, hd) gather form and the flat
    (b, mp*page, hkv, hd) bitcast view) and the (b, hkv, mp, group, hd)
    f32 per-page decode partials of the pre-fusion two-pass kernel."""
    mp = max_seq // page_size
    hkv, g, hd = cfg.num_kv_heads, cfg.group_size, cfg.head_dim
    return [f"f32[{max_batch},{mp},{page_size},{hkv},{hd}]",
            f"f32[{max_batch},{max_seq},{hkv},{hd}]",
            f"f32[{max_batch},{hkv},{mp},{g},{hd}]"]


def lowered_paged_hlo(cfg: ModelConfig, which: str = "decode", *,
                      max_batch: int = 2, max_seq: int = 64,
                      page_size: int = 8, prefill_chunk: int = 8,
                      params=None, sampling: SamplingState | None = None
                      ) -> str:
    """Compile the jitted paged serving step (`which` in {"decode",
    "prefill"}) on the current backend and return the optimized HLO
    text, for shape-structure analysis via `launch/hlo_analysis`.

    The fused-kernel acceptance checks and `benchmarks/serve_throughput
    --json` grep this text: the single-pass kernels must not write the
    (b, hkv, max_pages, group, hd) f32 decode partials nor materialize
    the (b, max_pages*page, hkv, hd) gathered prefill KV copy.  The
    sampling-API acceptance greps the ENTRY signature: int32 tokens, not
    (b, vocab) logits, leave the step (no host round-trip for
    sampling)."""
    fam = registry.get_family(cfg)
    if params is None:
        params = fam.init(jax.random.key(0), cfg)
    if sampling is None:
        sampling = greedy_state(max_batch)
    num_pages = max_batch * max_seq // page_size
    arena = fam.init_paged_cache(cfg, num_pages + 1, page_size, max_batch)
    bt = jnp.zeros((max_batch, max_seq // page_size), jnp.int32)
    zeros_b = jnp.zeros((max_batch,), jnp.int32)
    prefill_fn, decode_fn = make_paged_serve_fns(cfg)
    if which == "decode":
        lowered = decode_fn.lower(params, arena, bt, zeros_b, zeros_b,
                                  sampling)
    elif which == "prefill":
        chunk = {"tokens": jnp.zeros((max_batch, prefill_chunk), jnp.int32)}
        if cfg.frontend == "patch":
            chunk["patches"] = jnp.zeros(
                (max_batch, prefill_chunk, cfg.frontend_dim), jnp.float32)
        lowered = prefill_fn.lower(params, chunk, arena, bt, zeros_b, zeros_b,
                                   sampling)
    else:
        raise ValueError(which)
    return lowered.compile().as_text()
