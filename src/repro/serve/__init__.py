"""Paged-native serving on the UniMem arena.

Architecture (one pooled memory, the paper's form):

    core/unimem.py           host control plane: page pool, refcounts,
                             per-sequence page tables, copy-on-write
    serve/kv_cache.py        device arena (+ null page) and COW copies
    kernels/paged_attention  Pallas flash-decoding through block tables
    models/<family>          paged hooks: init_paged_cache /
                             paged_prefill / paged_decode_step
    serve/serve_step.py      jitted closures over the hooks
    serve/sampling.py        SamplingParams -> per-slot SamplingState;
                             greedy/temperature/top-k/top-p compiled
                             into the step (tokens, not logits, leave)
    serve/prefix_store.py    refcounted cross-request prefix cache:
                             parent-linked hash chains, LRU eviction
                             under the watermark, host-DRAM cold spill
    serve/speculative.py     speculative decode: draft models (truncated
                             self-draft or a paired small model) propose
                             k-token windows, one batched paged verify
                             call accepts/rejects them exactly
    serve/engine.py          continuous batching: lazy allocation,
                             chunked prefill, prefix sharing, preemption,
                             the TokenEvent/FinishEvent stream
    serve/tracing.py         host spans of the tick's phases and each
                             request's queue wait, in a bounded ring,
                             also written into a running profiler's trace
    serve/api.py             public facade: LLMServer.generate ->
                             GenerationStream (+ fork under a new
                             sampling regime over shared COW pages,
                             stream.cancel() mid-flight reclaim)
    serve/frontend/          network front (DESIGN.md §10): stdlib
                             HTTP + SSE streaming over the engine,
                             per-tenant weighted max-min budget shares,
                             client disconnect -> cancel -> page reclaim
                             (import repro.serve.frontend explicitly;
                             kept out of this namespace so batch users
                             pay nothing for the socket layer)

Every decode family except pure-SSM serves from the paged arena (KV
bytes scale with tokens in flight): dense, moe (expert dispatch inside
the paged decode step), vlm (patch-embedding chunks feed the paged text
cache), hybrid (attention KV share paged, conv/SSM state contiguous per
slot).  The ssm family's O(1) state cache uses the contiguous per-slot
fallback behind the same engine API.
"""
from repro.serve.kv_cache import (
    PagedKVArena,
    paged_write,
    paged_decode_attention,
    gather_pages,
    insert_slot,
    clear_slot,
)
from repro.serve.serve_step import (
    make_serve_fns, make_paged_serve_fns, make_paged_verify_fn,
    sample_logits, init_cache)
from repro.serve.sampling import (
    SamplingParams, SamplingState, sample_tokens, state_for_slots,
    greedy_state, expand_state, verify_tokens)
from repro.serve.speculative import DraftModel
from repro.serve.prefix_store import PrefixStore, PrefixEntry
from repro.serve.engine import (
    ServingEngine, Request, Result, TokenEvent, FinishEvent)
from repro.serve.api import LLMServer, GenerationStream
