"""Continuous-batching serving engine, paged-native on the UniMem arena.

The paper's serving claim made concrete: ONE pooled near-memory system
(the page arena) backs every sequence's KV cache.  Pages stay resident;
per step only the queries and tiny softmax summaries travel.  For
families with paged hooks (transformer) the engine is **paged-native**:

  * pages are allocated LAZILY as sequences grow — admission reserves
    the prompt's pages only, so pool memory tracks tokens in flight,
    not `max_batch * max_seq`;
  * prompt-prefix pages are SHARED across requests through a page-hash
    cache + `SequencePageTable.fork()` refcounts, with copy-on-write on
    partial last pages (`PagedKVArena.cow_for_write`);
  * long prefills are CHUNKED — each engine step advances admissions by
    one chunk while the fused decode step keeps running, so a long
    prompt never stalls tokens for active sequences;
  * when the pool runs dry mid-decode the YOUNGEST sequence is
    preempted back to the queue (recompute-on-readmit), which turns
    OOM into backpressure.

Prefill is BATCHED and BUCKETED.  One jit call per engine tick advances
EVERY admitting slot: the tick builds a single (max_batch, c) chunk
where row i belongs to slot i, non-admitting rows are inert
(chunk_len 0, null block tables), and each admitting row carries its own
ragged chunk_len.  The shared width c is snapped UP to a small fixed
bucket set — powers of two from 8 to `prefill_chunk` — so a ragged
prompt mix compiles at most `len(prefill_buckets)` prefill variants
instead of one per distinct prompt length (the jit cache stays bounded
no matter the workload; `prefill_shapes` records what was dispatched).

SAMPLING runs inside the jitted step.  Every request carries
`SamplingParams` (serve/sampling.py: greedy / temperature / top-k /
top-p, per-request seed, token budget, stop set); each tick the engine
lowers the live slots to a per-slot `SamplingState` struct-of-arrays and
the compiled step returns int32 TOKENS — the host never sees logits,
never argmaxes.  Randomness is counter-derived (`fold_in(key(seed),
emission_index)`), so tokens are a pure function of (prompt, params):
identical across batch compositions, slot order, shard counts, and
preempt/resume replays.

With `speculate_k > 0` the engine decodes SPECULATIVELY
(serve/speculative.py): a cheap draft model proposes a k-token window
per slot, the window is appended onto the slot's own page chain (the
shared boundary page COW-forked first, tail pages fresh), ONE batched
paged-prefill verify call judges every window, and in-step
accept/reject emits the matched prefix plus a bonus token — the
rejected page tail truncates back off the table.  The determinism
contract makes acceptance EXACT-MATCH against the target's own
counter-keyed draw, so the emitted stream is byte-identical to plain
decode; speculation only changes how many tokens one tick yields.

The engine is a TOKEN STREAM: every emitted token is published as a
`TokenEvent` and every retirement as a `FinishEvent` through ONE
emission path; `events()` drains them, `stream()` ticks the engine and
yields them, and `run()` survives as a thin compat wrapper that
exhausts the stream and returns the collected `Result`s.  The
`serve/api.py` facade (`LLMServer.generate` -> `GenerationStream`) sits
on this drain.

Scheduling is TOKEN-BUDGET driven when `prefill_decode_ratio` is set:
each tick has `tick_token_budget` tokens, split ratio:(1-ratio) between
the batched prefill call (chunk lengths capped oldest-first) and decode
(slots decoded oldest-first) — prefill/decode fairness as one knob.
The default (None) keeps the legacy full-speed behavior: full chunks
for every admitting slot plus a decode for every active slot.

Every decode family except pure-SSM serves paged-native: dense, moe
(expert dispatch inside the paged decode step), vlm (patch-embedding
chunks feed the paged text cache) and hybrid (attention KV share paged;
conv/SSM state contiguous per slot inside the arena).  The ssm family's
cache is O(1) state with nothing to page — it uses the contiguous
layout: per-slot caches with the pool as an admission counter over max
footprints.

Admission is WATERMARK-based: a request enters once its first prefill
chunk fits (low watermark), prompt pages then grow lazily chunk by
chunk; an optional high watermark preempts youngest slots before the
pool runs hard dry.

Given a mesh with a "mem" axis (>1 device), the arena is SHARDED
near-memory style (`serve/sharded/`): every chip owns a static bank of
pages, the allocator interleaves each sequence's pages across banks
under a per-prompt shard ROTATION (hash of the first full page — bank
balance for short prompts, prefix partners stay aligned), queries
broadcast and only (b, hq, hd)-sized softmax summaries cross the
interconnect.  The engine logic here is identical either way — it
talks global page ids; the jitted step localizes them.

Loop shape (classic continuous batching):

    while work:
        admit: free slot + admissible request -> slot enters PREFILL
        prefill: ONE bucketed jit call advancing all prefilling slots
        step:  one fused decode step over ALL active slots
        retire: eos / token-budget slots -> emit result, free pages
"""
from __future__ import annotations

import time
import zlib
from collections import deque
from dataclasses import dataclass, field, replace

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.unimem import (HostParcel, HostTier, SequencePageTable,
                               UniMemOOM, UniMemPool)
from repro.models.config import ModelConfig
from repro.models import registry
from repro.serve.kv_cache import PagedKVArena, insert_slot, clear_slot
from repro.serve import tracing
from repro.serve.prefix_store import PrefixStore
from repro.serve.sampling import (SamplingParams, state_for_slots,
                                  sample as sample_on_device)
from repro.serve.serve_step import (make_serve_fns, make_paged_serve_fns,
                                    make_paged_verify_fn)
from repro.utils.logging import get_logger

log = get_logger("engine")


@dataclass
class Request:
    uid: int
    prompt: np.ndarray                 # (prompt_len,) int32
    max_new_tokens: int = 32           # legacy mirror of sampling.max_new_tokens
    eos_token: int = -1                # -1 = never; folded into sampling.stop
    tenant: str = "default"            # budget-share bucket (frontend/tenants)
    patch_embeds: np.ndarray | None = None   # vlm: (num_patches, frontend_dim)
    sampling: SamplingParams | None = None   # resolved by the engine at submit
    # tokens a preempted slot had already generated: on readmission the
    # engine REPLAYS them as forced context instead of re-sampling, so
    # published tokens can never be contradicted by a recompute (fork
    # children inherit tokens drawn under the PARENT's params — only a
    # forced replay reproduces those)
    replay: list[int] | None = None

    @property
    def num_patch_tokens(self) -> int:
        return 0 if self.patch_embeds is None else len(self.patch_embeds)

    @property
    def virtual_len(self) -> int:
        """Prompt positions the cache must hold: image rows + tokens."""
        return self.num_patch_tokens + len(self.prompt)

    @property
    def max_footprint(self) -> int:
        return self.virtual_len + self.max_new_tokens

    def virtual_bytes(self, lo: int, hi: int) -> bytes:
        """Content of virtual positions [lo, hi) for page hashing."""
        p = self.num_patch_tokens
        parts = []
        if lo < p:
            parts.append(self.patch_embeds[lo:min(hi, p)].tobytes())
        if hi > p:
            parts.append(self.prompt[max(lo - p, 0):hi - p].tobytes())
        return b"".join(parts)


@dataclass
class Result:
    uid: int
    tokens: list[int]
    prompt_len: int
    admitted_at: float = 0.0
    finished_at: float = 0.0
    finish_reason: str = "length"      # "length" | "stop" | "cancelled"

    @property
    def latency_s(self) -> float:
        return self.finished_at - self.admitted_at


@dataclass(frozen=True)
class TokenEvent:
    """One generated token, published as it is emitted.  `index` is the
    emission index within its request (0 = first generated token) —
    exactly-once per (uid, index): a preempted slot's recompute replays
    silently."""
    uid: int
    token: int
    index: int


@dataclass(frozen=True)
class FinishEvent:
    """A request retired; carries the full `Result` and why it stopped."""
    uid: int
    reason: str                        # "length" | "stop" | "cancelled"
    result: Result


@dataclass
class _Slot:
    request: Request
    pages: SequencePageTable                 # paged: live table; contig: reservation
    generated: list[int] = field(default_factory=list)
    last_token: int = 0
    admitted_at: float = 0.0
    order: int = 0                           # admission sequence number
    prefill_pos: int = 0                     # prompt tokens already in pages
    shared_tokens: int = 0                   # of which reused from the prefix cache
    page_hashes: list[int] = field(default_factory=list)
    # prefix-store hashes this slot holds a reference on (acquired at
    # admission / absorb / self-registration, released at retire/preempt)
    store_refs: set[int] = field(default_factory=set)
    # speculative decode: context tokens the DRAFT cache row has
    # consumed for this slot (-1 = row never synced for this tenant —
    # the first sync resets it, clearing any previous occupant's state)
    draft_pos: int = -1

    @property
    def prefilling(self) -> bool:
        return self.prefill_pos < self.request.virtual_len


class ServingEngine:
    """`layout="paged"` (default where the family supports it) serves
    from the UniMem arena; `layout="contiguous"` is the per-slot
    fallback.  Both run the same continuous-batching loop and publish
    the same event stream.

    Chunk bucketing
    ---------------
    Paged prefill advances all admitting slots in ONE jit call per tick:
    a (max_batch, c) token chunk where row i is slot i and each row
    carries its own ragged `chunk_len`.  The shared width c is snapped
    UP to `prefill_buckets` — powers of two from 8 up to
    `prefill_chunk`, plus `prefill_chunk` itself (e.g. chunk 32 ->
    [8, 16, 32]).  Because batch and width are the only shape-bearing
    dims, the engine compiles at most len(prefill_buckets) prefill
    variants for ANY workload, instead of one per distinct prompt
    length; `prefill_shapes` records the (batch, width) pairs actually
    dispatched.  Rows with fewer remaining tokens than the bucket mask
    their tails (writes to the null page, logits at the last valid
    position), so bucketing never changes emitted tokens."""

    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 8,
                 max_seq: int = 1024, page_size: int = 16,
                 pool_pages: int | None = None, temperature: float = 0.0,
                 layout: str | None = None, prefill_chunk: int | None = None,
                 mesh=None, high_watermark: float | None = None,
                 prefill_decode_ratio: float | None = None,
                 tick_token_budget: int | None = None,
                 host_tier_pages: int | None = None,
                 prefix_cache: bool = False,
                 speculate_k: int = 0, draft: str | None = None,
                 tenant_weights: dict[str, float] | None = None):
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.page_size = page_size
        # engine-wide default temperature for requests submitted without
        # explicit SamplingParams (legacy constructor knob)
        self.default_temperature = temperature
        # a mesh with a >1 "mem" axis shards the arena near-memory style
        # (pages resident per chip, queries broadcast, summaries merged);
        # a 1-device mesh degrades to the plain single-arena path, so
        # every existing code path is untouched.
        from repro.launch.mesh import MEM_AXIS
        self.mesh = None
        if (mesh is not None and MEM_AXIS in getattr(mesh, "axis_names", ())
                and mesh.shape[MEM_AXIS] > 1):
            self.mesh = mesh
        # fraction of pool pages above which the engine proactively
        # preempts youngest slots (None = preempt only on hard OOM)
        self.high_watermark = high_watermark
        # per-tenant weighted max-min budget shares (frontend/tenants.py):
        # passing a tenant->weight dict (even {}; unnamed tenants weigh
        # 1.0) turns the token-budget tick and watermark admission
        # multi-tenant — prefill chunk caps, decode row caps and the
        # admission order all follow the weighted shares, enforced
        # INSIDE the existing tick.  Tenant scheduling needs a budget to
        # divide, so it defaults the prefill/decode ratio on.
        self.tenants = None
        self.tenant_tokens: dict[str, int] = {}
        if tenant_weights is not None:
            from repro.serve.frontend.tenants import TenantScheduler
            self.tenants = TenantScheduler(tenant_weights)
            if prefill_decode_ratio is None:
                prefill_decode_ratio = 0.5
        fam = registry.get_family(cfg)
        if fam.decode_step is None:
            raise ValueError(f"family {cfg.family!r} cannot serve (no decode)")
        self.fam = fam
        self._patch_frontend = cfg.frontend == "patch"
        if layout is None:
            layout = "paged" if registry.has_paged(cfg) else "contiguous"
        if layout == "paged" and not registry.has_paged(cfg):
            raise ValueError(f"family {cfg.family!r} has no paged path")
        if layout not in ("paged", "contiguous"):
            raise ValueError(f"unknown layout {layout!r}")
        self.layout = layout
        if layout != "paged":
            self.mesh = None        # only the arena shards; contiguous
                                    # (ssm fallback) serves single-device
        if self.mesh is not None:
            # every chip of the mesh holds the weights: place them once,
            # or each step would broadcast them from where they were made
            from jax.sharding import NamedSharding, PartitionSpec
            self.params = params = jax.device_put(
                params, NamedSharding(self.mesh, PartitionSpec()))
        pool_pages = pool_pages or (max_batch * max_seq) // page_size
        self.max_pages = -(-max_seq // page_size)     # block-table width
        self.prefill_chunk = prefill_chunk or max(page_size * 4, 32)
        # token-budget tick: ratio of each tick's token budget given to
        # the batched prefill call; the remainder caps decoded slots.
        # None = legacy full-speed (full chunks + every active slot).
        if prefill_decode_ratio is not None \
                and not 0.0 <= prefill_decode_ratio <= 1.0:
            raise ValueError(
                f"prefill_decode_ratio must be in [0, 1], got "
                f"{prefill_decode_ratio}")
        self.prefill_decode_ratio = prefill_decode_ratio
        self.tick_token_budget = (tick_token_budget
                                  or max_batch * self.prefill_chunk)
        # chunk widths snap UP to this fixed set: powers of two from 8 to
        # prefill_chunk (plus prefill_chunk itself) — the jit cache for
        # prefill is bounded by len(prefill_buckets), not by the number
        # of distinct prompt lengths in the workload.
        self.prefill_buckets = sorted(
            {1 << b for b in range(3, self.prefill_chunk.bit_length())
             if (1 << b) < self.prefill_chunk} | {self.prefill_chunk})
        self.prefill_shapes: set[tuple[int, int]] = set()

        if layout == "paged":
            if self.mesh is not None:
                from repro.serve.sharded import (ShardedPagedKVArena,
                                                 make_sharded_serve_fns)
                n = self.mesh.shape[MEM_AXIS]
                pool_pages = -(-pool_pages // n) * n   # round UP: never
                                                       # shrink the pool
                self.arena = ShardedPagedKVArena(
                    cfg, num_pages=pool_pages, page_size=page_size,
                    max_batch=max_batch, mesh=self.mesh)
                self.prefill_fn, self.decode_fn = make_sharded_serve_fns(
                    cfg, self.mesh, pool_pages,
                    arena_keys=tuple(self.arena.kv))
            else:
                self.arena = PagedKVArena(cfg, num_pages=pool_pages,
                                          page_size=page_size,
                                          max_batch=max_batch)
                self.prefill_fn, self.decode_fn = make_paged_serve_fns(cfg)
            self.pool = self.arena.pool
            # families with contiguous per-slot state (hybrid conv/SSM)
            # can share page MEMORY but never skip prefill COMPUTE: the
            # skipped tokens' state would not exist for the new slot
            self._slot_state = self.arena.state_bytes > 0
            self.cache = None
            # host-DRAM cold tier: preempted slots spill their written KV
            # pages here instead of burning a full recompute on
            # readmission (families with per-slot recurrent state keep
            # the replay path — their conv/SSM rows can't be restored
            # into a different slot)
            self.host_tier = (HostTier(host_tier_pages)
                              if host_tier_pages else None)
            # refcounted prompt-page cache keyed by chained content
            # hashes (DESIGN.md §8).  persistent=True keeps entries
            # alive at refcount 0 — pinned in the pool, reclaimed by LRU
            # eviction under the watermark/OOM shed paths — so a request
            # can hit the prefix of a donor that retired long ago;
            # persistent=False (default) reproduces the legacy
            # donor-lifetime semantics through the same store.
            self.prefix_store = PrefixStore(
                self.pool, persistent=prefix_cache, arena=self.arena,
                host_tier=self.host_tier)
            # uid -> (parcel, device-resident copy of its page data);
            # filled by the async head-of-queue prefetch in step()
            self._prefetched: dict[int, tuple] = {}
        else:
            self.host_tier = None
            self.prefix_store = None
            self._prefetched = {}
            self.arena = None
            self.cache = fam.init_cache(cfg, max_batch, max_seq)
            self.cache_ax = fam.cache_axes()
            self.pool = UniMemPool(pool_pages, page_size)
            # temperature only parameterizes decode_many (unused here);
            # the decode closure samples from the per-slot SamplingState
            # — the engine-wide default folds in via _resolve_sampling
            self.prefill_fn, self.decode_fn, _ = make_serve_fns(cfg)

        # speculative decode: a draft model proposes `speculate_k`-token
        # windows, ONE batched paged-prefill verify call judges them
        # (serve/speculative.py).  `draft` picks the draft spec
        # ("self:N" / "<arch>[@reduced]"; None = registry pairing).
        self.speculate_k = int(speculate_k or 0)
        self.draft = None
        self.verify_fn = None
        self.fused_fn = None
        self.spec_stats = dict(windows=0, draft_tokens=0, verify_calls=0,
                               accepted_tokens=0, emitted_tokens=0)
        if self.speculate_k > 0:
            if self.layout != "paged":
                raise ValueError("speculative decode requires the paged "
                                 "layout")
            if not registry.has_verify(cfg):
                raise ValueError(f"family {cfg.family!r} cannot be a "
                                 f"speculative-decode target")
            from repro.serve.speculative import DraftModel
            self.draft = DraftModel(cfg, params, draft,
                                    max_batch=max_batch, max_seq=max_seq)
            if self.mesh is not None:
                from repro.serve.sharded import make_sharded_verify_fn
                self.verify_fn = make_sharded_verify_fn(
                    cfg, self.mesh, self.pool.num_pages,
                    arena_keys=tuple(self.arena.kv))
            else:
                # rewindable drafts run propose+verify+rewind as ONE
                # jitted dispatch; state drafts keep the two-call path
                # (their rollback replays from a host-held checkpoint)
                self.fused_fn = self.draft.fused_fn(self.speculate_k)
                if self.fused_fn is None:
                    self.verify_fn = make_paged_verify_fn(cfg)

        self.pending: list[Request] = []
        self.slots: dict[int, _Slot] = {}        # slot index -> state
        self.results: list[Result] = []
        self.steps = 0
        self.tokens_out = 0
        self.prefill_tokens = 0          # prompt tokens actually computed
        self.preemptions = 0             # slots kicked back to the queue
        self.cancellations = 0           # requests cancelled mid-flight
        self._admitted = 0
        self._events: deque = deque()
        self._emitted: dict[int, int] = {}       # uid -> tokens published
        # spans (serve/tracing.py): this engine's id, and when each
        # queued request (re-)entered `pending`, for its `engine.queue`
        self.trace_id = tracing.next_engine_id()
        self._queued_at: dict[int, int] = {}     # uid -> perf_counter_ns

    # ------------------------------------------------------------ intake

    def _resolve_sampling(self, request: Request) -> None:
        """Fill in `request.sampling` (legacy fields -> params) and keep
        the legacy mirrors coherent — the engine reads `sampling` only.
        EVERY legacy field folds into explicit params the same way: an
        eos_token joins the stop set, and a non-default max_new_tokens
        overrides a params-default budget (explicit params win when both
        are set away from their defaults)."""
        sp = request.sampling
        if sp is None:
            stop = (request.eos_token,) if request.eos_token >= 0 else ()
            sp = SamplingParams(temperature=self.default_temperature,
                                max_new_tokens=request.max_new_tokens,
                                stop=stop)
        else:
            if request.eos_token >= 0 and request.eos_token not in sp.stop:
                sp = replace(sp, stop=sp.stop + (request.eos_token,))
            default_budget = SamplingParams().max_new_tokens
            if (request.max_new_tokens != default_budget
                    and sp.max_new_tokens == default_budget):
                sp = replace(sp, max_new_tokens=request.max_new_tokens)
        request.sampling = sp.validate()
        request.max_new_tokens = sp.max_new_tokens

    def submit(self, request: Request):
        self._resolve_sampling(request)
        if request.max_footprint > self.max_seq:
            raise ValueError(
                f"request {request.uid}: footprint {request.max_footprint} "
                f"> max_seq {self.max_seq}")
        if self._patch_frontend and (request.num_patch_tokens
                                     != self.cfg.num_patches):
            raise ValueError(
                f"request {request.uid}: {self.cfg.family} requests need "
                f"patch_embeds with {self.cfg.num_patches} rows, got "
                f"{request.num_patch_tokens}")
        self._queued_at[request.uid] = time.perf_counter_ns()
        self.pending.append(request)

    def _dequeue(self, pidx: int) -> Request:
        """Take `pending[pidx]` off the queue to admit it; its wait since
        it (re-)entered the queue becomes an `engine.queue` span."""
        req = self.pending.pop(pidx)
        t0 = self._queued_at.pop(req.uid, None)
        if t0 is not None:
            tracing.record("engine.queue", t0, time.perf_counter_ns(),
                           uid=req.uid)
        return req

    def _free_slots(self) -> list[int]:
        return [i for i in range(self.max_batch) if i not in self.slots]

    # ---------------------------------------------------- event emission

    def _emit(self, s: _Slot, tok: int) -> None:
        """THE single token-emission path — paged decode, contiguous
        decode and the prefill first token all land here.  Appends to
        the slot, counts, and publishes a TokenEvent exactly once per
        (uid, index): a preempted slot's recompute replays its earlier
        tokens without re-publishing them."""
        s.generated.append(tok)
        s.last_token = tok
        self.tokens_out += 1
        t = s.request.tenant
        self.tenant_tokens[t] = self.tenant_tokens.get(t, 0) + 1
        idx = len(s.generated) - 1
        uid = s.request.uid
        if idx >= self._emitted.get(uid, 0):
            self._emitted[uid] = idx + 1
            self._events.append(TokenEvent(uid=uid, token=tok, index=idx))

    def _next_token(self, s: _Slot, sampled: int) -> int:
        """The slot's next token: the step's sampled output, unless the
        slot is REPLAYING tokens it had generated before a preemption —
        forced replay reproduces published history exactly (a fork
        child's inherited tokens were drawn under the PARENT's params;
        re-sampling them under its own would contradict the stream)."""
        rep = s.request.replay
        if rep is not None:
            t = len(s.generated)
            if t < len(rep):
                return rep[t]
            s.request.replay = None              # replay complete
        return sampled

    def _emit_decoded(self, active: dict[int, _Slot], next_tokens) -> None:
        """Shared tail of both decode layouts: the wait for the step's
        tokens, then their emission, each a span of its own."""
        with tracing.span("engine.decode.readback"):
            next_tokens = np.asarray(next_tokens)
        with tracing.span("engine.decode.emit"):
            for i, s in active.items():
                self._emit(s, self._next_token(s, int(next_tokens[i])))

    def events(self) -> list:
        """Drain pending TokenEvent/FinishEvent records (FIFO)."""
        out = list(self._events)
        self._events.clear()
        return out

    # ---------------------------------------------------------- sampling

    def _sampling_state(self, rows: dict[int, _Slot]):
        """Lower the live rows to the per-slot SamplingState threaded
        through the jitted step.  The emission counter is the number of
        tokens generated so far — token t is always drawn with
        fold_in(key(seed), t), whatever batch/slot/tick it lands in."""
        return state_for_slots(
            self.max_batch,
            [(i, s.request.sampling, len(s.generated))
             for i, s in rows.items()])

    # ------------------------------------------------- prefix page cache

    def _page_hashes(self, req: Request) -> list[int]:
        """Chained content hashes of the virtual prompt's FULL pages
        (vLLM-style: each page's identity includes everything before it;
        vlm patch-embedding rows hash like tokens)."""
        ps = self.page_size
        out, h = [], 0
        for i in range(req.virtual_len // ps):
            h = hash((h, req.virtual_bytes(i * ps, (i + 1) * ps)))
            out.append(h)
        return out

    def _rotation_of(self, req: Request) -> int:
        """Per-prompt shard rotation (sharded pools only): a STABLE hash
        of the first (full, if present) page's content offsets the
        sequence's logical->shard stride, so page 0 of many short
        prompts spreads over all banks instead of concentrating on
        shard 0.  Content-derived, so prefix-sharing partners (same
        first page) rotate identically and shared pages keep their
        shard; crc32 (not Python's salted hash()) keeps placement and
        per-shard metrics reproducible across processes."""
        n = getattr(self.pool, "num_shards", 1)
        if n <= 1:
            return 0
        head = req.virtual_bytes(0, min(self.page_size, req.virtual_len))
        return zlib.crc32(head) % n

    def _match_prefix(self, req: Request) -> tuple[list[int], list[int],
                                                   list[int], int | None,
                                                   list[int]]:
        """Longest run of shareable full pages for this prompt, capped so
        at least one prompt position is always re-prefilled (it produces
        the first-token logits).  Returns (written, adopted, hashes,
        rot_hint, store_hashes): `written` pages hold published K/V the
        new sequence can skip; `adopted` pages extend the run with pages
        a PREFILLING slot has allocated for identical content — not yet
        (fully) written, so the new sequence still prefills through
        them, but both rows write the same values into the same physical
        pages (batched co-prefill is pure memory dedup; once the leader
        publishes a page the follower's `_absorb_shared` skips the
        recompute).  Store matches may come from retired donors
        (persistent cache) and even from cold host-tier parcels restored
        on the spot; `rot_hint` is the donor's shard rotation the
        follower must adopt, and `store_hashes` names the matched
        entries the admitting slot must acquire references on."""
        hashes = self._page_hashes(req)
        limit = (req.virtual_len - 1) // self.page_size
        written, adopted, store_hashes = [], [], []
        rot_hint = None
        store = self.prefix_store
        for i, h in enumerate(hashes[:limit]):
            page = store.page_of(h)
            if page is None and not adopted and not self._slot_state:
                # device miss: a cold copy may still sit in the host tier
                page = store.restore_cold(h, i)
            if page is not None:
                if rot_hint is None:
                    rot_hint = store.rotation_of(h)
                store_hashes.append(h)
                # per-slot-state families (hybrid) must recompute every
                # prompt token — published pages are adopted, not skipped
                if not adopted and not self._slot_state:
                    written.append(page)
                else:                      # keep the run contiguous
                    adopted.append(page)
                continue
            page = next((s.pages.pages[i] for s in self.slots.values()
                         if s.prefilling and i < len(s.page_hashes)
                         and s.page_hashes[i] == h
                         and i < len(s.pages.pages)), None)
            if page is None:
                break
            adopted.append(page)
        return written, adopted, hashes, rot_hint, store_hashes

    def _register_prefix(self, slot: _Slot):
        """Publish the slot's prompt pages for future sharing — only the
        pages whose K/V the prefill has fully WRITTEN (registering at
        admission would let a second request attend to still-empty
        pages).  The store takes its own pool reference per entry and
        the slot acquires one for itself, so refcounts stay the
        number-of-live-tables invariant the law battery pins."""
        store = self.prefix_store
        full = min(slot.request.virtual_len, slot.prefill_pos) // self.page_size
        for i, h in enumerate(slot.page_hashes[:full]):
            if i >= len(slot.pages.pages):
                break
            mine = slot.pages.pages[i]
            page = store.page_of(h)
            if page is None:
                parent = slot.page_hashes[i - 1] if i else None
                store.register(h, mine, parent=parent, index=i,
                               rotation=slot.pages.rotation)
                page = mine
            if page == mine and h not in slot.store_refs:
                store.acquire(h)
                slot.store_refs.add(h)

    def _absorb_shared(self, s: _Slot):
        """Late-binding prefix sharing: a slot that was admitted before a
        matching prompt finished prefilling can still adopt the published
        pages — swap its own (not yet written) pages for the shared ones
        and skip those chunks.  Only at page-aligned prefill positions.
        Never for per-slot-state families: skipping tokens would leave
        the slot's conv/SSM state behind its page contents."""
        if self._slot_state:
            return
        ps = self.page_size
        store = self.prefix_store
        limit = (s.request.virtual_len - 1) // ps
        while s.prefill_pos % ps == 0:
            i = s.prefill_pos // ps
            if i >= limit or i >= len(s.page_hashes) \
                    or i >= len(s.pages.pages):
                break
            h = s.page_hashes[i]
            page = store.page_of(h)
            if page is None:
                break
            if page == s.pages.pages[i]:
                # co-prefill adoption: the page is already ours and the
                # donor has now fully written it — skip the recompute,
                # keep the pool ref we took at admission, and take a
                # store ref now that we lean on the published entry
                if h not in s.store_refs:
                    store.acquire(h, reuse=True)
                    s.store_refs.add(h)
                s.prefill_pos += ps
                s.shared_tokens += ps
                continue
            self.pool.share([page])
            self.pool.free([s.pages.pages[i]])   # ours was never written
            s.pages.pages[i] = page
            store.acquire(h, reuse=True)
            s.store_refs.add(h)
            s.prefill_pos += ps
            s.shared_tokens += ps

    def _drop_store_refs(self, s: _Slot) -> None:
        """The slot's table is going away: release its prefix-store
        references.  Persistent entries outlive the slot (pinned idle at
        refcount 0, LRU-evictable); transient entries die with the last
        referencing slot — the legacy lifetime, one code path."""
        for h in s.store_refs:
            self.prefix_store.release(h)
        s.store_refs.clear()

    def _release_pages(self, seq: SequencePageTable):
        """Free a table.  Prefix-store entries hold their own pool
        reference, so registered pages can never dangle behind the
        store's back — a dying table just drops its refs and the
        hash<->page maps stay consistent by construction (the stale
        `_page_hash` bug of the flat-dict cache is structurally gone;
        tests/test_prefix_store.py pins the invariant)."""
        seq.release()

    # ---------------------------------------------------- cache reclaim

    def _reclaim_idle(self, need: int = 1, start: int | None = None,
                      protect: set[int] | None = None) -> int:
        """LRU-evict idle (refcount-0) prefix-store pages to make room —
        the watermark/OOM shed paths try this BEFORE preempting live
        slots, because dropping cached prefixes costs a future
        re-prefill while preemption costs a present one.  `start` aims
        eviction at the banks a strided alloc at that logical index
        would demand (sharded pools); a pool-wide pass backstops it.
        Returns pages actually freed."""
        store = self.prefix_store
        if store is None or not len(store):
            return 0
        shards = None
        n = getattr(self.pool, "num_shards", 1)
        if start is not None and n > 1:
            shards = {(start + k) % n for k in range(min(need, n))}
        freed = store.evict(need, shards=shards, protect=protect)
        if freed < need and shards is not None:
            freed += store.evict(need - freed, protect=protect)
        return freed

    def _fits_or_reclaim(self, start: int, need: int,
                         protect: set[int] | None = None) -> bool:
        """`pool.fits`, with idle cache pages counted as reclaimable
        headroom: evict-and-retry until the alloc fits or the idle set
        is dry (matched entries in `protect` are never victims — their
        pages are about to be adopted)."""
        while not self.pool.fits(start, need):
            if not self._reclaim_idle(need, start, protect=protect):
                return False
        return True

    # ------------------------------------------------------------- admit

    def _admit(self):
        if self.layout == "paged":
            self._admit_paged()
        else:
            self._admit_contiguous()

    def _next_admission(self) -> int:
        """Index into `pending` of the next admission candidate.  FIFO
        without tenant scheduling; with it, the head request of the
        tenant with the smallest weighted slot occupancy (max-min over
        HELD slots — the admission-time analogue of the tick's budget
        shares).  FIFO within a tenant, so a preempted request (queue
        front) keeps its priority; FIFO across equal occupancies, so
        single-tenant behavior is exactly the legacy order."""
        if self.tenants is None or len(self.pending) <= 1:
            return 0
        held: dict[str, int] = {}
        for s in self.slots.values():
            t = s.request.tenant
            held[t] = held.get(t, 0) + 1
        best, best_key = 0, None
        seen: set[str] = set()
        for j, r in enumerate(self.pending):
            if r.tenant in seen:
                continue
            seen.add(r.tenant)
            key = held.get(r.tenant, 0) / self.tenants.weight_of(r.tenant)
            if best_key is None or key < best_key:
                best, best_key = j, key
        return best

    def _admit_paged(self):
        """Watermark-based admission: a request enters as soon as the
        pool can hold its FIRST prefill chunk (the low-watermark
        estimate), not its whole prompt — the remaining prompt pages are
        allocated lazily, one chunk per tick, exactly like decode
        growth, with preemption as the backpressure.  A prompt that only
        fits once a draining slot retires no longer waits for the
        retire: it prefills INTO the freeing pool.  Shared prefix pages
        cost nothing extra."""
        free = self._free_slots()
        while free and self.pending:
            pidx = self._next_admission()
            req = self.pending[pidx]
            if self.host_tier is not None and req.uid in self.host_tier:
                verdict = self._restore_from_tier(req, free, pidx)
                if verdict == "restored":
                    continue
                if verdict == "wait":
                    break               # pool must drain first
                # "recompute": parcel dropped, fall through to normal
                # admission (replay pinned at preemption still replays
                # the already-published tokens)
            plen = req.virtual_len
            written, adopted, hashes, rot_hint, store_hashes = \
                self._match_prefix(req)
            # a store hit binds the follower to the DONOR's shard
            # rotation: the cached pages live on the donor's banks, and
            # the jitted walk recovers rotation from the first block
            # table column — content-derived hashing makes the two
            # values equal, the adoption makes the invariant structural
            rot = rot_hint if rot_hint is not None else self._rotation_of(req)
            shared_tokens = len(written) * self.page_size
            # adopted pages are held but still prefilled through (their
            # content lands when this row — or the co-prefilling donor —
            # writes them); only `written` tokens are skipped outright
            held = shared_tokens + len(adopted) * self.page_size
            first = min(self.prefill_chunk, plen - held)
            need = (self.pool.pages_for(held + first)
                    - len(written) - len(adopted))
            if not self._fits_or_reclaim(rot + len(written) + len(adopted),
                                         need, protect=set(store_hashes)):
                break                            # UniMem backpressure
            self._dequeue(pidx)
            slot = free.pop(0)
            if written or adopted:
                self.pool.share(written + adopted)
            for h in store_hashes:
                self.prefix_store.acquire(h, reuse=True)
            seq = SequencePageTable(self.pool, written + adopted, held,
                                    rotation=rot)
            seq.append_tokens(first)
            s = _Slot(request=req, pages=seq, admitted_at=time.perf_counter(),
                      order=self._admitted, prefill_pos=shared_tokens,
                      shared_tokens=shared_tokens, page_hashes=hashes,
                      store_refs=set(store_hashes))
            self._admitted += 1
            self.slots[slot] = s
            self._register_prefix(s)    # shared pages are already written

    def _admit_contiguous(self):
        free = self._free_slots()
        while free and self.pending:
            req = self.pending[0]
            if not self.pool.can_admit(req.max_footprint):
                break                            # UniMem backpressure
            self._dequeue(0)
            slot = free.pop(0)
            pages = SequencePageTable(self.pool)
            pages.append_tokens(req.max_footprint)
            # batch=1 prefill, then insert into the shared cache at `slot`
            one_cache = self.fam.init_cache(self.cfg, 1, self.max_seq)
            batch = {"tokens": jnp.asarray(req.prompt, jnp.int32)[None, :]}
            if req.patch_embeds is not None:
                batch["patch_embeds"] = jnp.asarray(req.patch_embeds)[None]
            with tracing.span("engine.prefill.dispatch", rows=1, batch=1):
                one_cache, logits = self.prefill_fn(self.params, batch,
                                                    one_cache)
            self.prefill_tokens += req.virtual_len
            self.cache = insert_slot(self.cache, one_cache, slot, self.cache_ax)
            s = _Slot(request=req, pages=pages,
                      admitted_at=time.perf_counter(), order=self._admitted,
                      prefill_pos=req.virtual_len)
            # the first token samples ON DEVICE too (emission counter 0)
            first = sample_on_device(
                logits, state_for_slots(1, [(0, req.sampling, 0)]))
            self._emit(s, int(np.asarray(first)[0]))
            self.slots[slot] = s
            self._admitted += 1

    # ----------------------------------------------------------- prefill

    def _bucket_width(self, n: int) -> int:
        """Smallest fixed bucket >= n (n <= prefill_chunk by construction)."""
        return next(b for b in self.prefill_buckets if b >= n)

    def _prefill_token_budget(self) -> int | None:
        """This tick's prompt-token allowance (None = unlimited).  When
        nothing is decoding, an idle decode share rolls over to prefill
        so a ratio of 0 can never deadlock admission."""
        if self.prefill_decode_ratio is None:
            return None
        budget = int(self.prefill_decode_ratio * self.tick_token_budget)
        decoding = any(not s.prefilling and s.generated
                       for s in self.slots.values())
        if budget < 1 and not decoding:
            budget = self.prefill_chunk
        return budget

    def _decode_slot_budget(self) -> int | None:
        """Max slots decoded this tick (None = all active).  At least
        one, so decode always progresses."""
        if self.prefill_decode_ratio is None:
            return None
        b = self.tick_token_budget
        return max(1, b - int(self.prefill_decode_ratio * b))

    def _prefill_tick(self):
        """Advance EVERY prefilling slot by one ragged chunk in a SINGLE
        jit call (paged layout).  Row i of the (max_batch, c) chunk
        belongs to slot i; rows that are decoding or empty are inert
        (chunk_len 0, null block tables).  The shared width c is the
        smallest bucket covering the longest pending chunk, so the
        number of distinct compiled prefill shapes is bounded by
        `prefill_buckets` however ragged the prompt mix.  Decode over
        already-active slots proceeds in the same engine step, so long
        prompts never freeze token emission.  Under a token-budget tick
        the chunk lengths are additionally capped oldest-first by the
        prefill share of `tick_token_budget`."""
        if self.layout != "paged" or not any(
                s.prefilling for s in self.slots.values()):
            return
        with tracing.span("engine.prefill.build"):
            batch = self._prefill_batch()
        if batch is None:
            return
        pre, chunk, bt, start, clen, st = batch
        b, c = chunk["tokens"].shape
        walk = self._walk_counts(np.where(clen > 0, start + clen - 1, -1))
        with tracing.span("engine.prefill.dispatch", rows=len(pre), batch=b,
                          **walk):
            self.arena.kv, first = self.prefill_fn(
                self.params, chunk, self.arena.kv, bt, start, clen, st)
        self.prefill_shapes.add((b, c))
        self.prefill_tokens += int(clen.sum())
        with tracing.span("engine.prefill.readback"):
            first = np.asarray(first)
        with tracing.span("engine.prefill.emit"):
            for i, s in pre:
                s.prefill_pos += int(clen[i])
                self._register_prefix(s)         # newly-written full pages
                if not s.prefilling:             # prompt complete: the
                                                 # step sampled token 0
                    self._emit(s, self._next_token(s, int(first[i])))

    def _walk_counts(self, last) -> dict:
        """The `blocks` and `slots` counts of a paged dispatch span: the
        page blocks the fused kernels compute, each row up to the block
        holding its last query position (`last`, negative for a row
        with none; `kernels/paged_attention.live_blocks`), and the
        blocks a walk of every row's whole table would.  On a `mem`
        mesh both count the unsharded walk."""
        ppb = max(1, min(self.cfg.attn_pages_per_block, self.max_pages))
        blocks = np.where(last >= 0, last // (self.page_size * ppb) + 1, 0)
        return dict(blocks=int(blocks.sum()),
                    slots=len(last) * -(-self.max_pages // ppb))

    def _prefill_batch(self):
        """This tick's prefill rows and the step's arguments: (rows,
        chunk, block table, start, chunk_len, sampling state), or None
        when no row advances."""
        pre = [(i, s) for i, s in self.slots.items() if s.prefilling]
        for _, s in pre:
            self._absorb_shared(s)
        pre = [(i, s) for i, s in pre if s.prefilling]
        if not pre:
            return None
        lens = {i: min(self.prefill_chunk,
                       s.request.virtual_len - s.prefill_pos)
                for i, s in pre}
        budget = self._prefill_token_budget()
        if budget is not None:
            caps = None
            if self.tenants is not None:
                # weighted max-min shares of the prefill budget over the
                # tenants with prefilling slots; chunk lengths then cap
                # oldest-first WITHIN each tenant's share
                demands: dict[str, int] = {}
                for i, s in pre:
                    t = s.request.tenant
                    demands[t] = demands.get(t, 0) + lens[i]
                caps = self.tenants.allocate(budget, demands,
                                             kind="prefill")
            for i, s in sorted(pre, key=lambda kv: kv[1].order):
                if caps is None:
                    lens[i] = min(lens[i], max(budget, 0))
                    budget -= lens[i]
                else:
                    t = s.request.tenant
                    lens[i] = min(lens[i], max(caps.get(t, 0), 0))
                    caps[t] = caps.get(t, 0) - lens[i]
            pre = [(i, s) for i, s in pre if lens[i] > 0]
            if not pre:
                return None
        # lazy prompt-page growth (watermark admission allocated only the
        # first chunk): extend each slot's table to cover this tick's
        # chunk, preempting younger slots under pool pressure — a slot
        # preempted here simply sits out the tick
        for i, s in pre:
            if self.slots.get(i) is not s:
                continue                         # preempted this tick
            grow = s.prefill_pos + lens[i] - s.pages.num_tokens
            if grow > 0:
                self._with_preemption(
                    s, lambda s=s, g=grow: s.pages.append_tokens(g))
        pre = [(i, s) for i, s in pre if self.slots.get(i) is s]
        if not pre:
            return None
        lens = {i: lens[i] for i, _ in pre}
        b, c = self.max_batch, self._bucket_width(max(lens.values()))
        tokens = np.zeros((b, c), np.int32)
        start = np.zeros((b,), np.int32)
        clen = np.zeros((b,), np.int32)
        bt = np.full((b, self.max_pages), self.arena.null_page, np.int32)
        patches = (np.zeros((b, c, self.cfg.frontend_dim), np.float32)
                   if self._patch_frontend else None)
        for i, s in pre:
            req, n, pos = s.request, lens[i], s.prefill_pos
            p = req.num_patch_tokens
            lo = max(pos, p)                 # first text position in chunk
            if lo < pos + n:
                tokens[i, lo - pos:n] = req.prompt[lo - p:pos + n - p]
            if patches is not None and pos < p:
                hi = min(pos + n, p)
                patches[i, :hi - pos] = req.patch_embeds[pos:hi]
            start[i] = pos
            clen[i] = n
            bt[i, :len(s.pages.pages)] = s.pages.pages
        # np args throughout the hot-path calls: pjit's C++ fastpath
        # converts them far cheaper than explicit device_puts
        chunk = {"tokens": tokens}
        if patches is not None:
            chunk["patches"] = patches
        return pre, chunk, bt, start, clen, self._sampling_state(dict(pre))

    # ------------------------------------------------------------- step

    def _with_preemption(self, s: _Slot, fn) -> bool:
        """Run one ATOMIC allocator step (raises UniMemOOM before any
        mutation) under the age-priority discipline: a slot may evict
        only YOUNGER slots.  With no younger victim left it yields —
        preempts ITSELF back to the queue (returns False; the caller
        must skip the slot this tick).  Mutual old↔young eviction would
        otherwise livelock under watermark admission (the victim
        readmits next tick and evicts its evictor); strict age order
        means the oldest slot always runs to completion.  A lone slot
        that still cannot fit surfaces the OOM — the pool is genuinely
        too small."""
        while True:
            try:
                fn()
                return True
            except UniMemOOM:
                # idle cached prefixes go first: reclaiming them costs a
                # future re-prefill, preempting a live slot costs one now
                if self._reclaim_idle():
                    continue
                if self._preempt_youngest(but=s):
                    continue
                if len(self.slots) > 1:          # yield to the elders
                    idx = next(i for i, sl in self.slots.items() if sl is s)
                    self._preempt_slot(idx, s)
                    return False
                raise

    def _grow_for_write(self, s: _Slot) -> None:
        """Lazy page growth + COW before this step's token write, each
        retried separately under pool pressure — retrying them as a unit
        would re-run the append after a COW OOM and double-count the
        token."""
        if not self._with_preemption(s, lambda: s.pages.append_tokens(1)):
            return                               # slot yielded its pages
        self._with_preemption(s, lambda: self.arena.cow_for_write(s.pages))

    def _preempt_slot(self, idx: int, victim: _Slot) -> None:
        """Kick one slot back to the queue front (recompute-on-readmit)
        and reclaim its pages."""
        log.info("engine: preempting uid=%d (pool pressure)",
                 victim.request.uid)
        self.preemptions += 1
        # pin what was already generated: readmission replays these as
        # forced context (never re-samples published history)
        if len(victim.generated) > len(victim.request.replay or ()):
            victim.request.replay = list(victim.generated)
        self._spill_slot(victim)                 # host tier, if enabled
        self._drop_store_refs(victim)
        self._release_pages(victim.pages)
        del self.slots[idx]
        self._queued_at[victim.request.uid] = time.perf_counter_ns()
        self.pending.insert(0, victim.request)

    def _preempt_youngest(self, but: _Slot) -> bool:
        """Preempt the most recently admitted slot YOUNGER than `but`
        (age priority — see _with_preemption)."""
        victims = [(i, s) for i, s in self.slots.items()
                   if s is not but and s.order > but.order]
        if not victims:
            return False
        idx, victim = max(victims, key=lambda kv: kv[1].order)
        self._preempt_slot(idx, victim)
        return True

    # --------------------------------------------------------- host tier

    def _spill_slot(self, victim: _Slot) -> None:
        """Copy the victim's WRITTEN KV pages to the host-DRAM cold tier
        so readmission restores them instead of recomputing.  Families
        with per-slot recurrent state (hybrid conv/SSM) never spill —
        their state rows can't be rebuilt in a different slot, so they
        keep the replay path."""
        tier = self.host_tier
        if tier is None or self._slot_state:
            return
        if victim.prefilling:
            valid = victim.prefill_pos
        elif victim.generated:
            # the LAST generated token's KV is written next decode tick
            valid = victim.request.virtual_len + len(victim.generated) - 1
        else:
            valid = 0
        if valid <= 0:
            return
        npages = self.pool.pages_for(valid)
        pages = victim.pages.pages[:npages]
        if len(pages) < npages:
            return
        data = self.arena.read_pages(pages)
        meta = dict(tokens=valid, prefill_pos=victim.prefill_pos,
                    rotation=victim.pages.rotation,
                    generated=list(victim.generated),
                    last_token=victim.last_token,
                    page_hashes=list(victim.page_hashes))
        self._prefetched.pop(victim.request.uid, None)   # stale copy
        tier.put(HostParcel(uid=victim.request.uid, num_pages=npages,
                            data=data, meta=meta))

    def _restore_from_tier(self, req, free: list[int],
                           pidx: int = 0) -> str:
        """Readmission fast path: rebuild the slot from its spilled
        parcel — fresh pages on the SAME shard rotation, page contents
        written back (prefetched device copy when the async prefetch
        landed), generation state resumed exactly.  Returns "restored",
        "wait" (pool must drain first) or "recompute" (parcel unusable —
        dropped; caller falls through to normal admission)."""
        tier = self.host_tier
        parcel = tier.peek(req.uid)
        rot = parcel.meta["rotation"]
        npages = parcel.num_pages
        # thrash guard: restoring straight past the shedder's limit
        # would preempt (and re-spill) somebody next tick.  Pinned-but-
        # idle cache pages do not count against the limit — they are
        # reclaimable headroom, evicted (not preempted) on demand.
        if self.high_watermark is not None and self.slots:
            limit = int(self.high_watermark * self.pool.num_pages)
            hot = (self.pool.num_pages - self.pool.free_pages
                   - self.pool.pinned_pages)
            if hot + npages > limit:
                return "wait"
        if not self._fits_or_reclaim(rot, npages):
            if self.slots:
                return "wait"
            tier.take(req.uid)          # pool genuinely too small
            return "recompute"
        tier.take(req.uid)
        tier.restores += 1
        tier.restored_pages += npages
        pre = self._prefetched.pop(req.uid, None)
        payload = pre[1] if pre is not None and pre[0] is parcel \
            else parcel.data
        self._dequeue(pidx)
        slot = free.pop(0)
        seq = SequencePageTable(self.pool, rotation=rot)
        seq.append_tokens(parcel.meta["tokens"])
        for j, pg in enumerate(seq.pages):
            self.arena.write_page(pg, {n: a[:, j] for n, a in
                                       payload.items()})
        s = _Slot(request=req, pages=seq,
                  generated=list(parcel.meta["generated"]),
                  last_token=parcel.meta["last_token"],
                  admitted_at=time.perf_counter(), order=self._admitted,
                  prefill_pos=parcel.meta["prefill_pos"],
                  page_hashes=list(parcel.meta["page_hashes"]))
        self._admitted += 1
        # KV restored byte-for-byte: published history needs no replay
        req.replay = None
        self.slots[slot] = s
        self._register_prefix(s)
        log.info("engine: restored uid=%d from host tier (%d pages)",
                 req.uid, npages)
        return "restored"

    def _tier_prefetch(self) -> None:
        """Async readmission prefetch: start moving the head-of-queue
        request's parcel back to device while this tick's compute runs
        (`jax.device_put` is asynchronous — the copy overlaps)."""
        tier = self.host_tier
        if tier is None or not self.pending:
            return
        uid = self.pending[self._next_admission()].uid
        if uid in self._prefetched:
            return
        parcel = tier.peek(uid)
        if parcel is None:
            return
        self._prefetched[uid] = (parcel, {
            n: jax.device_put(jnp.asarray(a))
            for n, a in parcel.data.items()})
        tier.prefetches += 1

    def _decode_rows(self) -> dict[int, _Slot]:
        """Active decode rows for this tick, throttled oldest-first by
        the decode share of the token budget (when a ratio is set).
        PAGED layout only: the contiguous fused step writes KV and
        advances `pos` for every batch row unconditionally, so excluding
        a row there would corrupt its cache — the ssm fallback always
        decodes every active slot."""
        active = {i: s for i, s in self.slots.items() if not s.prefilling
                  and s.generated}
        budget = (self._decode_slot_budget() if self.layout == "paged"
                  else None)
        if budget is None:
            return active
        if self.tenants is not None:
            # per-tenant row shares of the decode budget (max-min,
            # weighted); rows keep oldest-first WITHIN their tenant.
            # budget >= 1 guarantees some tenant holds a positive cap,
            # so decode always progresses.
            demands: dict[str, int] = {}
            for s in active.values():
                t = s.request.tenant
                demands[t] = demands.get(t, 0) + 1
            caps = self.tenants.allocate(budget, demands, kind="decode")
            keep: dict[int, _Slot] = {}
            for i, s in sorted(active.items(), key=lambda kv: kv[1].order):
                t = s.request.tenant
                if caps.get(t, 0) > 0:
                    keep[i] = s
                    caps[t] -= 1
            return keep
        if len(active) > budget:
            keep = sorted(active.items(), key=lambda kv: kv[1].order)[:budget]
            active = dict(keep)
        return active

    def _decode_paged(self):
        if self.draft is None:
            self._decode_plain(self._decode_rows())
            return
        spec, plain = self._partition_decode()
        self._decode_plain(plain)
        self._speculate(spec)

    def _decode_plain(self, active: dict[int, _Slot]):
        if not active:
            return
        with tracing.span("engine.decode.build"):
            # grow tables first (may preempt younger slots under pool
            # pressure)
            for i, s in list(active.items()):
                if self.slots.get(i) is not s:
                    continue                     # already preempted this step
                self._grow_for_write(s)
            active = {i: s for i, s in active.items()
                      if self.slots.get(i) is s}
            if not active:
                return
            tokens = np.zeros((self.max_batch,), np.int32)
            positions = np.zeros((self.max_batch,), np.int32)
            bt = np.full((self.max_batch, self.max_pages),
                         self.arena.null_page, np.int32)
            for i, s in active.items():
                tokens[i] = s.last_token
                positions[i] = s.pages.num_tokens - 1   # slot appended above
                bt[i, :len(s.pages.pages)] = s.pages.pages
            st = self._sampling_state(active)
        with tracing.span("engine.decode.dispatch", rows=len(active),
                          batch=self.max_batch,
                          **self._walk_counts(positions)):
            self.arena.kv, nxt = self.decode_fn(
                self.params, self.arena.kv, bt, positions, tokens, st)
        self._emit_decoded(active, nxt)

    # ------------------------------------------------- speculative decode

    def _partition_decode(self) -> tuple[dict[int, _Slot], dict[int, _Slot]]:
        """Split this tick's decode rows between the speculative-window
        path and plain one-token decode.  A row speculates when its
        request opted in (`SamplingParams.speculative`), it is not
        replaying pinned history (forced tokens would waste the window
        — and contradict it: replay bypasses sampling entirely), and
        its table has headroom for the k+1 candidate writes.  Under a
        token-budget tick a speculative row charges k+1 tokens against
        the decode share (its verify writes k+1 positions), oldest
        first; the oldest row always runs, so decode always
        progresses."""
        k = self.speculate_k
        active = {i: s for i, s in self.slots.items()
                  if not s.prefilling and s.generated}
        budget = self._decode_slot_budget()
        rows = sorted(active.items(), key=lambda kv: kv[1].order)
        wants_map = {i: (s.request.sampling.speculative
                         and s.request.replay is None
                         and s.pages.num_tokens + k + 1 <= self.max_seq)
                     for i, s in rows}
        caps = None
        if budget is not None and self.tenants is not None:
            # same per-tenant decode shares as the plain path, with a
            # speculative row charging its whole k+1 window against its
            # tenant (the verify writes k+1 positions)
            demands: dict[str, int] = {}
            for i, s in rows:
                t = s.request.tenant
                demands[t] = demands.get(t, 0) + ((k + 1) if wants_map[i]
                                                  else 1)
            caps = self.tenants.allocate(budget, demands, kind="decode")
        spec: dict[int, _Slot] = {}
        plain: dict[int, _Slot] = {}
        for i, s in rows:
            wants = wants_map[i]
            if caps is not None:
                t = s.request.tenant
                if caps.get(t, 0) <= 0:
                    continue            # a granted tenant always exists
                caps[t] -= (k + 1) if wants else 1
            elif budget is not None:
                if budget <= 0 and (spec or plain):
                    continue
                budget -= (k + 1) if wants else 1
            (spec if wants else plain)[i] = s
        return spec, plain

    def _speculate(self, spec: dict[int, _Slot]):
        """One draft/verify window over the speculating rows:

          1. SYNC the draft cache rows with their slots' context (rows
             that decoded through the plain path, fresh tenants, and
             fork children readmitted after preemption lag behind);
          2. PROPOSE: a (k+1)-step draft scan emits a k-token window
             per row, drawn with the slots' own counter-derived keys
             (Gumbel-coupled to the target draw);
          3. grow each slot's table for the k+1 candidate writes — COW
             the possibly-shared partial boundary page FIRST, then
             append (the appended tail pages are fresh allocations, so
             the later truncate can never strand a prefix partner);
          4. VERIFY: one batched paged-prefill walk writes all
             candidates' KV and returns the exact tokens plain decode
             would emit plus the matched-prefix length.  For rewindable
             drafts on a single arena, steps 2+4 (and the draft rewind)
             run as ONE fused dispatch (`DraftModel.fused_fn`) — the
             proposed window never visits the host;
          5. emit the accepted prefix + bonus token through the single
             `_emit` path, TRUNCATE the rejected page tail, and land
             the outcome in the draft cache (`rollback`)."""
        # plain decode ran first this tick and may have preempted
        # younger speculating slots under pool pressure
        spec = {i: s for i, s in spec.items() if self.slots.get(i) is s}
        if not spec:
            return
        k = self.speculate_k
        draft = self.draft
        with tracing.span("engine.verify.build"):
            entries = []
            for i, s in spec.items():
                # the draft's target context: every token except the
                # newest (s.last_token is the propose scan's first input)
                needed = s.request.virtual_len + len(s.generated) - 1
                reset = not 0 <= s.draft_pos <= needed
                pos = 0 if reset else s.draft_pos
                if reset or pos < needed:
                    ctx = np.concatenate(
                        [np.asarray(s.request.prompt, np.int32),
                         np.asarray(s.generated[:-1], np.int32)])
                    entries.append((i, ctx[pos:needed], reset))
                s.draft_pos = needed
            draft.sync(entries)

            last = np.zeros((self.max_batch,), np.int32)
            for i, s in spec.items():
                last[i] = s.last_token
            st = self._sampling_state(spec)
            # with a fused step (rewindable draft, single arena) the
            # propose scan runs INSIDE the verify dispatch — the window
            # never visits the host; otherwise draft first, verify second
            proposed = None
            if self.fused_fn is None:
                with tracing.span("engine.verify.propose"):
                    proposed = draft.propose(last, st, k)
            self.spec_stats["windows"] += len(spec)
            self.spec_stats["draft_tokens"] += len(spec) * k

            for i, s in list(spec.items()):
                if self.slots.get(i) is not s:
                    continue             # preempted growing an older slot
                if s.pages.num_tokens % self.page_size:
                    # the window's first write lands in the current
                    # partial last page — COW it BEFORE appending: the
                    # appended pages are fresh, so append-then-cow (the
                    # 1-token `_grow_for_write` order) would check the
                    # wrong page.  At a page boundary there is nothing to
                    # COW — every written page stays shared, every new
                    # page is private.
                    if not self._with_preemption(
                            s, lambda s=s: self.arena.cow_for_write(
                                s.pages)):
                        continue         # slot yielded its pages
                self._with_preemption(
                    s, lambda s=s: s.pages.append_tokens(k + 1))
            live = {i: s for i, s in spec.items() if self.slots.get(i) is s}

            # rows preempted mid-window (and rows that never speculated)
            # grow their draft context by 0 tokens: rollback restores
            # their pre-propose checkpoint state
            b = self.max_batch
            n = np.zeros((b,), np.int32)
            target = np.zeros((b, k + 1), np.int32)
            start = np.zeros((b,), np.int32)
            newest = np.full((b,), -1, np.int32)  # inert rows walk nothing
            bt = np.full((b, self.max_pages), self.arena.null_page, np.int32)
            for i, s in live.items():
                start[i] = s.pages.num_tokens - (k + 1)
                newest[i] = start[i] + k
                bt[i, :len(s.pages.pages)] = s.pages.pages
            if live and self.fused_fn is None:
                tokens = np.zeros((b, k + 1), np.int32)
                clen = np.zeros((b,), np.int32)
                for i, s in live.items():
                    tokens[i, 0] = s.last_token
                    tokens[i, 1:] = proposed[i]
                    clen[i] = k + 1
                live_st = self._sampling_state(live)
        if live:
            with tracing.span("engine.verify.dispatch", rows=len(live),
                              batch=b, **self._walk_counts(newest)):
                if self.fused_fn is not None:
                    mask = np.zeros((b,), bool)
                    mask[list(live)] = True
                    (self.arena.kv, draft.cache, target,
                     accept) = self.fused_fn(
                        self.params, draft.params, draft.cache,
                        last, st, self.arena.kv, bt, start, mask)
                else:
                    self.arena.kv, target, accept = self.verify_fn(
                        self.params, {"tokens": tokens}, self.arena.kv,
                        bt, start, clen, proposed, live_st)
            with tracing.span("engine.verify.readback"):
                target = np.asarray(target)
                accept = np.asarray(accept)
            self.spec_stats["verify_calls"] += 1
        with tracing.span("engine.verify.emit"):
            for i, s in live.items():
                sp = s.request.sampling
                emitted = 0
                for j in range(int(accept[i]) + 1):
                    tok = int(target[i, j])
                    self._emit(s, tok)
                    emitted += 1
                    if tok in sp.stop \
                            or len(s.generated) >= sp.max_new_tokens:
                        break            # the slot retires this tick
                # drop the rejected tail: positions start..start+emitted-1
                # hold the KV of [last, t_0..t_{emitted-2}] — exactly the
                # written-positions invariant (the newest emitted token's
                # KV is pending); the freed pages were appended above,
                # never shared, never registered
                s.pages.truncate(int(start[i]) + emitted)
                s.draft_pos += emitted
                n[i] = emitted
                self.spec_stats["accepted_tokens"] += int(accept[i])
                self.spec_stats["emitted_tokens"] += emitted
            if proposed is not None:
                draft.rollback(target, n)
        # the fused step already landed its rewind in-jit (pos grows by
        # accept+1 on live rows): a row that emitted FEWER tokens hit a
        # stop or its budget and retires this tick, so its stale draft
        # row never serves again — no correction needed

    def _decode_contiguous(self):
        active = self._decode_rows()
        if not active:
            return
        with tracing.span("engine.decode.build"):
            tokens = np.zeros((self.max_batch,), np.int32)
            for i, s in active.items():
                tokens[i] = s.last_token
            st = self._sampling_state(active)
        with tracing.span("engine.decode.dispatch", rows=len(active),
                          batch=self.max_batch):
            self.cache, nxt = self.decode_fn(self.params, self.cache,
                                             tokens, st)
        self._emit_decoded(active, nxt)

    def _finish_slot(self, i: int, s: _Slot, reason: str) -> Result:
        """THE single slot-retirement path — natural retires (`_retire`)
        and mid-flight cancellation (`cancel`) both land here: emit the
        FinishEvent, free the pages, release the prefix-store refs, and
        clear the contiguous cache row (ssm fallback)."""
        result = Result(
            uid=s.request.uid, tokens=list(s.generated),
            prompt_len=len(s.request.prompt),
            admitted_at=s.admitted_at, finished_at=time.perf_counter(),
            finish_reason=reason)
        self.results.append(result)
        self._events.append(FinishEvent(uid=s.request.uid, reason=reason,
                                        result=result))
        self._emitted.pop(s.request.uid, None)
        if self.layout == "paged":
            self._drop_store_refs(s)
            self._release_pages(s.pages)
        else:
            s.pages.release()               # pages back to the one pool
            self.cache = clear_slot(self.cache, i, self.cache_ax)
        del self.slots[i]
        return result

    def _retire(self):
        for i, s in list(self.slots.items()):
            if s.prefilling or not s.generated:
                continue
            sp = s.request.sampling
            stopped = s.generated[-1] in sp.stop
            if not stopped and len(s.generated) < sp.max_new_tokens:
                continue
            self._finish_slot(i, s, "stop" if stopped else "length")

    # ------------------------------------------------------------ cancel

    def cancel(self, uid: int, reason: str = "cancelled") -> bool:
        """Cancel a request mid-flight — the network front's client-
        disconnect path, exposed to in-process callers too.  Whatever
        state the request is in, every resource it holds comes back:

          * queued (incl. preempted-back-to-queue): dequeued, its
            host-tier parcel and prefetched device copy dropped;
          * active slot (prefilling OR decoding): retired through the
            SAME `_finish_slot` path as a natural finish — pages freed,
            prefix-store refs released (persistent entries survive at
            refcount 0 as designed), contiguous cache row cleared.

        Publishes a FinishEvent with reason "cancelled" carrying the
        tokens generated so far.  Returns False when the uid is unknown
        or already finished (cancellation after finish is a no-op, not
        an error — the disconnect race makes that ordinary)."""
        for j, r in enumerate(self.pending):
            if r.uid != uid:
                continue
            self.pending.pop(j)
            self._queued_at.pop(uid, None)
            if self.host_tier is not None:
                self.host_tier.take(uid)         # drop the cold parcel
            self._prefetched.pop(uid, None)
            result = Result(
                uid=uid, tokens=list(r.replay or ()),
                prompt_len=len(r.prompt),
                admitted_at=time.perf_counter(),
                finished_at=time.perf_counter(), finish_reason=reason)
            self.results.append(result)
            self._events.append(FinishEvent(uid=uid, reason=reason,
                                            result=result))
            self._emitted.pop(uid, None)
            self.cancellations += 1
            log.info("engine: cancelled uid=%d (queued)", uid)
            return True
        for i, s in list(self.slots.items()):
            if s.request.uid != uid:
                continue
            if self.host_tier is not None:
                self.host_tier.take(uid)         # stale parcel, if any
            self._prefetched.pop(uid, None)
            self._finish_slot(i, s, reason)
            self.cancellations += 1
            log.info("engine: cancelled uid=%d (active, %d tokens in)",
                     uid, len(s.generated))
            return True
        return False

    def _enforce_high_watermark(self):
        """Proactive backpressure: when allocation crosses the high
        watermark, preempt youngest slots (never the oldest — progress
        is guaranteed) until the pool is back under.  OOM-driven
        preemption still backstops a high_watermark of None."""
        if self.high_watermark is None or self.layout != "paged":
            return
        limit = int(self.high_watermark * self.pool.num_pages)

        def over():
            return (self.pool.num_pages - self.pool.free_pages) > limit

        # idle cache pages shed first — this is the LRU-under-watermark
        # reclaim of the persistent prefix store (cheapest memory to
        # give back: no live slot loses work, the cost is a possible
        # future re-prefill, softened by the host-tier cold spill)
        while over() and self._reclaim_idle():
            pass
        while over() and len(self.slots) > 1:
            oldest = min(self.slots.values(), key=lambda s: s.order)
            if not self._preempt_youngest(but=oldest):
                break

    def step(self):
        """One tick, traced as an `engine.step` span (its phases are
        child spans; serve/tracing.py)."""
        with tracing.span("engine.step", engine=self.trace_id,
                          tick=self.steps):
            with tracing.span("engine.admit"):
                self._admit()
            with tracing.span("engine.tier_prefetch"):
                self._tier_prefetch()   # overlap host->device copy with compute
            self._prefill_tick()
            self._enforce_high_watermark()
            if self.layout == "paged":
                self._decode_paged()
            else:
                self._decode_contiguous()
            self.steps += 1
            with tracing.span("engine.retire"):
                self._retire()

    def stream(self, max_steps: int = 10_000):
        """Tick the engine and yield TokenEvent/FinishEvent records as
        they happen — the streaming drain `serve/api.py` sits on."""
        while (self.pending or self.slots) and self.steps < max_steps:
            self.step()
            yield from self.events()

    def run(self, max_steps: int = 10_000) -> list[Result]:
        """Run to completion — a thin compat wrapper that exhausts the
        event stream and returns the collected Results."""
        t0 = time.perf_counter()
        for _ in self.stream(max_steps):
            pass
        dt = time.perf_counter() - t0
        if dt > 0:
            log.info("engine[%s]: %d results, %d tokens, %.1f tok/s, "
                     "pool util %.2f (peak %d pages)",
                     self.layout, len(self.results), self.tokens_out,
                     self.tokens_out / dt, self.pool.stats().utilization,
                     self.pool.stats().peak_allocated_pages)
        return self.results

    # -------------------------------------------------------------- fork

    def fork(self, uid: int, new_uid: int,
             sampling: SamplingParams | None = None) -> None:
        """Branch an active sequence into a free slot: the child SHARES
        every page (refcounts, zero copies) and diverges lazily — the
        first write into the shared partial last page triggers
        copy-on-write.  `sampling` gives the child its OWN regime
        (seed/temperature/top-k/top-p) over the shared prefix — one
        prompt decoded under several sampling laws from the same COW
        pages; None inherits the parent's.  Paged layout only."""
        if self.layout != "paged":
            raise ValueError("fork requires the paged layout")
        free = self._free_slots()
        if not free:
            raise RuntimeError("no free slot to fork into")
        src_i, src = next(((i, s) for i, s in self.slots.items()
                           if s.request.uid == uid), (None, None))
        if src is None or src.prefilling:
            raise ValueError(f"uid {uid} is not active")
        child_req = Request(uid=new_uid, prompt=src.request.prompt,
                            eos_token=src.request.eos_token,
                            patch_embeds=src.request.patch_embeds,
                            sampling=sampling or src.request.sampling)
        self._resolve_sampling(child_req)
        child = _Slot(request=child_req, pages=src.pages.fork(),
                      generated=list(src.generated),
                      last_token=src.last_token,
                      admitted_at=time.perf_counter(), order=self._admitted,
                      prefill_pos=child_req.virtual_len,
                      shared_tokens=src.pages.num_tokens,
                      store_refs=set(src.store_refs))
        # the child's table references the same registered prefix pages
        # as the parent — it takes its own store refs so eviction
        # accounting keeps seeing one reference per live table
        for h in child.store_refs:
            self.prefix_store.acquire(h)
        self._admitted += 1
        # inherited tokens were the parent's — the child's stream starts
        # at the fork point
        self._emitted[new_uid] = len(child.generated)
        self.slots[free[0]] = child
        # state that cannot share pages (hybrid conv/SSM rows) is copied
        self.arena.copy_slot_state(src_i, free[0])
        # the child's page_hashes stay EMPTY on purpose: its pages are
        # the parent's (plus COW'd speculative tails) — re-registering
        # them from the child would double-publish pages the parent
        # already owns in the store, and a retiring reject-heavy child
        # must never re-register hashes for pages it never wrote
        if self.draft is not None:
            self.draft.copy_row(src_i, free[0])
            child.draft_pos = src.draft_pos

    # ------------------------------------------------------------- stats

    def peak_kv_bytes(self) -> int:
        """Device bytes the cache layout actually ties down: the
        contiguous cache reserves its full footprint up front; the paged
        arena's cost is the page high-water mark plus any contiguous
        per-slot state (hybrid conv/SSM rows, zero elsewhere)."""
        if self.layout == "paged":
            return (self.pool.stats().peak_allocated_pages
                    * self.arena.page_bytes + self.arena.state_bytes)
        return sum(int(a.size) * a.dtype.itemsize
                   for a in jax.tree.leaves(self.cache))

    def stats(self) -> dict:
        out = {
            "layout": self.layout,
            "steps": self.steps,
            "tokens_out": self.tokens_out,
            "prefill_tokens": self.prefill_tokens,
            "active_slots": len(self.slots),
            "pending": len(self.pending),
            "admitted": self._admitted,
            "preemptions": self.preemptions,
            "cancellations": self.cancellations,
            "peak_kv_bytes": self.peak_kv_bytes(),
            "prefill_buckets": list(self.prefill_buckets),
            "prefill_shapes": sorted(self.prefill_shapes),
            "prefill_decode_ratio": self.prefill_decode_ratio,
            "pool": self.pool.stats().__dict__,
        }
        if self.tenants is not None:            # per-tenant budget shares
            out["tenants"] = {
                t: {"weight": self.tenants.weight_of(t),
                    "tokens": self.tenant_tokens.get(t, 0)}
                for t in sorted(set(self.tenant_tokens)
                                | set(self.tenants.weights))}
        if self.prefix_store is not None:       # prompt-page reuse traffic
            out["prefix_store"] = self.prefix_store.stats()
        if self.draft is not None:              # speculative decode traffic
            sp = dict(self.spec_stats)
            sp["k"] = self.speculate_k
            sp["accept_rate"] = (sp["accepted_tokens"] / sp["draft_tokens"]
                                 if sp["draft_tokens"] else 0.0)
            sp["draft"] = self.draft.stats()
            out["speculative"] = sp
        if self.mesh is not None:               # near-memory sharded arena
            out["shards"] = self.pool.shard_stats()
            out["shard_kv_bytes"] = self.arena.shard_kv_bytes()
        if self.host_tier is not None:          # DRAM cold tier traffic
            tier = self.host_tier.stats()
            tier["peak_bytes"] = (tier["peak_resident_pages"]
                                  * self.arena.page_bytes)
            out["host_tier"] = tier
        return out
