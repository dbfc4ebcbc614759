"""Jitted sharded serving steps: shard_map over the `mem` axis.

The single-arena closures of `serve/serve_step.py`, lifted onto a device
mesh (DESIGN.md §2).  The engine keeps talking GLOBAL pool page ids —
the jitted step translates them per shard:

  * the (b, max_pages) block table and the (b,)/(b, c) token inputs are
    tiny and REPLICATED (the broadcast query of the near-memory layout);
  * the family hooks receive the GLOBAL table and localize it
    themselves: page WRITES go through `layers.localize_block_table`
    (entries this shard owns become bank slots, everything else — other
    shards' pages, the null sentinel — its local null slot), while
    `cfg.mem_axis` flips the attention layer into the rotation-aware
    resident-stride walk + partials mode + cross-shard log-sum-exp merge
    (`models/layers.py` / `distribution/collectives.py`).  Keeping the
    global ids to the walk is what lets each shard recover a sequence's
    per-prompt shard ROTATION (the bank-balance fix) from the table
    itself — no extra step inputs;
  * out through the boundary travel only the updated LOCAL banks (which
    never move) and the replicated (b, vocab) logits, which the step
    immediately collapses to int32 tokens via the per-slot
    `SamplingState` — sampling happens in-jit, after the summary merge,
    identically on every shard.

Nothing page-sized ever crosses the interconnect — the HLO-structure
test pins that: every collective in the compiled step is summary-sized.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.unimem import is_page_leaf
from repro.launch.mesh import MEM_AXIS
from repro.models.config import ModelConfig
from repro.models import registry
from repro.serve.kv_cache import PAGED_KV_KEYS
from repro.serve.sampling import (SamplingState, greedy_state, sample_tokens,
                                  verify_tokens)


def make_sharded_serve_fns(cfg: ModelConfig, mesh: Mesh, num_pages: int,
                           *, arena_keys=tuple(PAGED_KV_KEYS)):
    """Sharded analogues of `make_paged_serve_fns` — same signatures,
    GLOBAL block tables, per-slot `SamplingState`; `num_pages` is the
    global pool size (fixes the static page→shard arithmetic).
    `arena_keys` names the family's arena leaves (non-KV leaves ride
    replicated)."""
    fam = registry.get_family(cfg)
    if not registry.has_paged(cfg):
        raise ValueError(f"family {cfg.family!r} has no paged serving path")
    n = mesh.shape[MEM_AXIS]
    if num_pages % n:
        raise ValueError(f"num_pages {num_pages} must divide over {n} shards")
    scfg = cfg.replace(mem_axis=MEM_AXIS)
    arena_specs = {k: (P(None, MEM_AXIS) if is_page_leaf(k) else P())
                   for k in arena_keys}
    rep = P()
    cpu = jax.default_backend() == "cpu"

    def prefill_body(params, chunk, arena, bt, start, clen):
        return fam.paged_prefill(params, scfg, chunk, arena, bt, start, clen)

    prefill_sharded = jax.shard_map(
        prefill_body, mesh=mesh,
        in_specs=(rep, rep, arena_specs, rep, rep, rep),
        out_specs=(arena_specs, rep), check_vma=False)

    def decode_body(params, arena, bt, positions, tokens):
        return fam.paged_decode_step(params, scfg, arena, bt, positions,
                                     tokens)

    decode_sharded = jax.shard_map(
        decode_body, mesh=mesh,
        in_specs=(rep, arena_specs, rep, rep, rep),
        out_specs=(arena_specs, rep), check_vma=False)

    @partial(jax.jit, donate_argnums=() if cpu else (2,))
    def prefill_chunk(params, chunk, arena, block_table, start, chunk_len,
                      sampling: SamplingState):
        arena, logits = prefill_sharded(params, chunk, arena, block_table,
                                        start, chunk_len)
        return arena, sample_tokens(logits, sampling)

    @partial(jax.jit, donate_argnums=() if cpu else (1,))
    def decode(params, arena, block_table, positions, tokens,
               sampling: SamplingState):
        arena, logits = decode_sharded(params, arena, block_table, positions,
                                       tokens)
        return arena, sample_tokens(logits, sampling)

    return prefill_chunk, decode


def make_sharded_verify_fn(cfg: ModelConfig, mesh: Mesh, num_pages: int,
                           *, arena_keys=tuple(PAGED_KV_KEYS)):
    """Sharded analogue of `serve_step.make_paged_verify_fn`: the verify
    walk runs per shard in partials mode (summary-sized merge, like
    prefill), the merged (b, k+1, vocab) logits come back replicated,
    and accept/reject collapses them to int32 in-jit — identical on
    every shard, so the accepted stream is byte-equal to one device."""
    fam = registry.get_family(cfg)
    if not registry.has_verify(cfg):
        raise ValueError(f"family {cfg.family!r} has no speculative-verify "
                         f"path")
    n = mesh.shape[MEM_AXIS]
    if num_pages % n:
        raise ValueError(f"num_pages {num_pages} must divide over {n} shards")
    scfg = cfg.replace(mem_axis=MEM_AXIS)
    arena_specs = {k: (P(None, MEM_AXIS) if is_page_leaf(k) else P())
                   for k in arena_keys}
    rep = P()
    cpu = jax.default_backend() == "cpu"

    def verify_body(params, chunk, arena, bt, start, clen):
        return fam.paged_verify(params, scfg, chunk, arena, bt, start, clen)

    verify_sharded = jax.shard_map(
        verify_body, mesh=mesh,
        in_specs=(rep, rep, arena_specs, rep, rep, rep),
        out_specs=(arena_specs, rep), check_vma=False)

    @partial(jax.jit, donate_argnums=() if cpu else (2,))
    def verify(params, chunk, arena, block_table, start, chunk_len, draft,
               sampling: SamplingState):
        arena, logits = verify_sharded(params, chunk, arena, block_table,
                                       start, chunk_len)
        target, accept = verify_tokens(logits, draft, sampling)
        return arena, target, accept

    return verify


def lowered_sharded_hlo(cfg: ModelConfig, mesh: Mesh, which: str = "decode",
                        *, max_batch: int = 2, max_seq: int = 64,
                        page_size: int = 8, prefill_chunk: int = 8,
                        params=None,
                        sampling: SamplingState | None = None) -> str:
    """Compile the jitted SHARDED serving step and return its optimized
    HLO text — the interconnect-contract check greps this: every
    collective op must be summary-sized (no page-sized operands cross
    the mesh), and the ENTRY signature carries int32 tokens, not
    logits."""
    from repro.serve.sharded.arena import ShardedPagedKVArena

    fam = registry.get_family(cfg)
    if params is None:
        params = fam.init(jax.random.key(0), cfg)
    if sampling is None:
        sampling = greedy_state(max_batch)
    n = mesh.shape[MEM_AXIS]
    num_pages = -(-max_batch * max_seq // page_size // n) * n
    arena = ShardedPagedKVArena(cfg, num_pages=num_pages,
                                page_size=page_size, max_batch=max_batch,
                                mesh=mesh)
    bt = jnp.zeros((max_batch, max_seq // page_size), jnp.int32)
    zeros_b = jnp.zeros((max_batch,), jnp.int32)
    prefill_fn, decode_fn = make_sharded_serve_fns(cfg, mesh, num_pages)
    if which == "decode":
        lowered = decode_fn.lower(params, arena.kv, bt, zeros_b, zeros_b,
                                  sampling)
    elif which == "prefill":
        chunk = {"tokens": jnp.zeros((max_batch, prefill_chunk), jnp.int32)}
        if cfg.frontend == "patch":
            chunk["patches"] = jnp.zeros(
                (max_batch, prefill_chunk, cfg.frontend_dim), jnp.float32)
        lowered = prefill_fn.lower(params, chunk, arena.kv, bt, zeros_b,
                                   zeros_b, sampling)
    else:
        raise ValueError(which)
    return lowered.compile().as_text()
