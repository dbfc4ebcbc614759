"""Paged-native serving: the fused Pallas block-table kernels (decode +
chunk prefill) against their oracles across tile and non-tile
geometries, HLO structure of the jitted steps (no bulk attention
buffers through HBM), and the engine's UniMem behaviours — lazy
allocation, prefix sharing, copy-on-write forks, OOM backpressure, and
tokens-in-flight memory scaling."""
from __future__ import annotations

import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.unimem import UniMemPool, SequencePageTable, UniMemOOM
from repro.models import registry
from repro.models import layers as L
from repro.kernels.paged_attention.ops import paged_decode_attention
from repro.kernels.paged_attention.ref import (
    paged_decode_attention_ref, paged_decode_attention_split_ref)
from repro.kernels.paged_prefill.ops import paged_prefill_attention
from repro.kernels.paged_prefill.ref import paged_prefill_attention_ref
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.serve import ServingEngine, Request
from repro.serve.kv_cache import PagedKVArena
from repro.serve.serve_step import (HLO_PROBE_GEOM, bulk_attn_shapes,
                                    lowered_paged_hlo)

from conftest import TINY


# --------------------------------------------- kernel == ref == contiguous

def _random_paged_setup(seed=0, b=3, hq=4, hkv=2, hd=16, page=8, mp=4):
    """Random arena + scattered block tables; last slot is the null page."""
    rng = np.random.default_rng(seed)
    P = b * mp + 1
    k_pages = jnp.asarray(rng.standard_normal((P, page, hkv, hd)), jnp.float32)
    v_pages = jnp.asarray(rng.standard_normal((P, page, hkv, hd)), jnp.float32)
    bt = jnp.asarray(rng.permutation(P - 1)[:b * mp].reshape(b, mp), jnp.int32)
    pos = jnp.asarray([mp * page - 1, 5, 17], jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, hq, hd)), jnp.float32)
    return q, k_pages, v_pages, bt, pos


def test_paged_kernel_matches_ref_and_contiguous():
    q, k_pages, v_pages, bt, pos = _random_paged_setup()
    got = paged_decode_attention(q, k_pages, v_pages, bt, pos,
                                 interpret=True)
    want_ref = paged_decode_attention_ref(q, k_pages, v_pages, bt, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_ref),
                               rtol=1e-5, atol=1e-5)
    # gather the pages contiguous and compare against the dense oracle
    b, mp = bt.shape
    page = k_pages.shape[1]
    kc = k_pages[bt].reshape(b, mp * page, *k_pages.shape[2:])
    vc = v_pages[bt].reshape(b, mp * page, *v_pages.shape[2:])
    want_contig = decode_attention_ref(q, kc, vc, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_contig),
                               rtol=1e-5, atol=1e-5)


def test_paged_kernel_ignores_null_page_tail():
    """Block-table tails pointing at the null page must not perturb the
    result for short sequences."""
    q, k_pages, v_pages, bt, pos = _random_paged_setup(seed=1)
    null = k_pages.shape[0] - 1
    # sequence 1 only needs 1 page (pos 5): null out its tail
    bt_nulled = bt.at[1, 1:].set(null)
    a = paged_decode_attention(q, k_pages, v_pages, bt, pos, interpret=True)
    b_ = paged_decode_attention(q, k_pages, v_pages, bt_nulled, pos,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(a[1]), np.asarray(b_[1]),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------- fused kernels: geometry matrix
#
# Non-tile geometries the TPU tiling pass must pad around: GQA groups
# below the 8-sublane tile, head dims off the 128-lane tile (both
# smaller and larger), multi-page grid cells (pages_per_block > 1,
# including widths that do not divide the block table), and ragged
# prefill chunk tails.  All interpret-mode vs the jnp refs.

GEOMETRIES = [
    # (hq, hkv, hd, page, mp, ppb)
    (4, 2, 16, 8, 4, 1),     # group 2 < 8 sublanes, hd 16 < 128 lanes
    (4, 4, 16, 8, 4, 2),     # group 1, two pages per grid cell
    (8, 2, 64, 4, 5, 2),     # ppb does not divide max_pages (padded tail)
    (16, 2, 160, 8, 3, 3),   # hd > 128 and not a lane multiple
    (8, 8, 128, 8, 2, 2),    # exact-tile MXU geometry (no padding path)
]



# ------------------------------------ which path the paged walk takes

@pytest.mark.parametrize("backend,impl,fused", [
    ("cpu", "flash_xla", False),      # the published configs' default
    ("cpu", "dense", False),
    ("cpu", "flash_pallas", True),    # the interpret-mode parity tests
    ("tpu", "flash_xla", True),
    ("tpu", "dense", True),
    ("gpu", "flash_xla", True),
])
def test_paged_walk_runs_fused_kernels_off_the_cpu(monkeypatch, backend,
                                                   impl, fused):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert L.fused_paged_kernels(TINY["dense"].replace(
        attention_impl=impl)) is fused


@pytest.mark.parametrize("backend", ["cpu", "tpu", "gpu", "unknown"])
@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_pallas_interpreter_serves_the_cpu_only(monkeypatch, backend, which):
    """The wrappers pick the interpreter for the CPU backend and the
    compiled kernel for every other, an unknown one included."""
    import repro.kernels.paged_attention.kernel as KD
    import repro.kernels.paged_prefill.kernel as KP
    from repro.kernels.paged_attention import ops as dec_ops
    from repro.kernels.paged_prefill import ops as pre_ops

    seen = []
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if which == "decode":
        monkeypatch.setattr(KD, "paged_decode_attention_pallas",
                            lambda *a, interpret, **k: seen.append(interpret))
        dec_ops.paged_decode_attention.__wrapped__(*[None] * 5)
    else:
        monkeypatch.setattr(KP, "paged_prefill_attention_pallas",
                            lambda *a, interpret, **k: seen.append(interpret))
        pre_ops.paged_prefill_attention.__wrapped__(*[None] * 6)
    assert seen == [backend == "cpu"]

def _geom_setup(rng, b, hd, page, mp, hkv):
    P = b * mp + 1
    k_pages = jnp.asarray(rng.standard_normal((P, page, hkv, hd)), jnp.float32)
    v_pages = jnp.asarray(rng.standard_normal((P, page, hkv, hd)), jnp.float32)
    bt = jnp.asarray(rng.permutation(P - 1)[:b * mp].reshape(b, mp), jnp.int32)
    return k_pages, v_pages, bt


@pytest.mark.parametrize("hq,hkv,hd,page,mp,ppb", GEOMETRIES)
def test_fused_decode_kernel_geometries(hq, hkv, hd, page, mp, ppb):
    rng = np.random.default_rng(hq * 1000 + hd)
    b = 3
    k_pages, v_pages, bt = _geom_setup(rng, b, hd, page, mp, hkv)
    pos = jnp.asarray(rng.integers(0, mp * page, b), jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, hq, hd)), jnp.float32)
    got = paged_decode_attention(q, k_pages, v_pages, bt, pos,
                                 pages_per_block=ppb, interpret=True)
    want = paged_decode_attention_ref(q, k_pages, v_pages, bt, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # the two-pass split oracle (per-page partials + shared combine)
    # must agree too — it checks the online log-sum-exp algebra
    split = paged_decode_attention_split_ref(q, k_pages, v_pages, bt, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(split),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("hq,hkv,hd,page,mp,ppb", GEOMETRIES)
def test_fused_prefill_kernel_geometries(hq, hkv, hd, page, mp, ppb):
    rng = np.random.default_rng(hq * 1000 + hd + 1)
    b, c = 3, 8
    k_pages, v_pages, bt = _geom_setup(rng, b, hd, page, mp, hkv)
    start = jnp.asarray(rng.integers(0, mp * page - c, b), jnp.int32)
    # ragged tails: one inert row (0), one partial, one full-width
    clen = jnp.asarray([0, int(rng.integers(1, c)), c], jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, c, hq, hd)), jnp.float32)
    got = paged_prefill_attention(q, k_pages, v_pages, bt, start, clen,
                                  pages_per_block=ppb, interpret=True)
    want = paged_prefill_attention_ref(q, k_pages, v_pages, bt, start, clen)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # ragged tail rows are exact zeros, not garbage
    assert np.all(np.asarray(got[0]) == 0.0)                 # clen 0
    assert np.all(np.asarray(got[1, int(clen[1]):]) == 0.0)  # partial tail


# Dead blocks: every page of a block wholly past its row's live range is
# filled with NaN (pages past the range inside a live block stay clean,
# the mask keeps them out but 0 * NaN would not).  A kernel that fetched
# and computed such a block would carry NaN into p @ v; the guarded walk
# must return what it returns over a clean tail, bit for bit.  Cases:
# every geometry at ppb 1 and at its own ppb (one that does not divide
# the table among them), then a shard's compacted table in partials mode
# and an int8 arena whose scale banks carry the NaN.

GUARD_CASES = ([(g, ppb, "plain") for g in GEOMETRIES for ppb in (1, g[-1])]
               + [(GEOMETRIES[2], 2, "partials"),
                  (GEOMETRIES[2], 2, "int8")])


def _live_blocks_by_hand(ppos, last, ppb):
    """Per row, 1 + the last block (of ppb table slots) holding a slot
    at or before the row's last query position; 0 where none does."""
    b, mp = ppos.shape
    out = []
    for i in range(b):
        live = [j for j in range(-(-mp // ppb))
                if (ppos[i, j * ppb:(j + 1) * ppb] <= last[i]).any()]
        out.append(max(live) + 1 if live else 0)
    return out


@pytest.mark.parametrize("kernel", ["decode", "prefill"])
@pytest.mark.parametrize("geom,ppb,variant", GUARD_CASES)
def test_dead_page_blocks_are_neither_fetched_nor_computed(kernel, geom,
                                                           ppb, variant):
    from repro.core.unimem import quantize_kv
    from repro.kernels.paged_attention.kernel import POS_PAD
    hq, hkv, hd, page, mp = geom[:5]
    rng = np.random.default_rng(hq * 1000 + hd + ppb)
    b, c = 3, 8
    k, v, bt = _geom_setup(rng, b, hd, page, mp, hkv)
    # slot j's first position: logical page j, or with "partials" the
    # odd logical pages shard 1 of 2 holds (so twice the span)
    lp = np.arange(mp) * page
    if variant == "partials":
        lp = (2 * np.arange(mp) + 1) * page
    span = int(lp[-1]) + page
    # rows: one whose first query is early, one live to its table's end,
    # one in between; the prefill's first row has chunk_len 0
    mid = int(rng.integers(0, span - c))
    if kernel == "decode":
        rows = (jnp.asarray([1, span - 1, mid], jnp.int32),)
        last = np.asarray(rows[0])
    else:
        clen = np.asarray([0, c, int(rng.integers(1, c))], np.int32)
        start = np.asarray([mid, span - c, mid], np.int32)
        rows = (jnp.asarray(start), jnp.asarray(clen))
        last = np.where(clen > 0, start + clen - 1, -1)
    ppos = np.broadcast_to(lp, (b, mp)).astype(np.int32)
    kw = {}
    if variant == "partials":
        # a compacted walk: POS_PAD for the pages not yet written and
        # for a hole another shard owns
        ppos = np.where(ppos <= last[:, None], ppos, POS_PAD)
        ppos[:, 1] = POS_PAD
        kw = dict(page_positions=jnp.asarray(ppos), partials=True)
    dead = [int(bt[i, j]) for i, n in
            enumerate(_live_blocks_by_hand(ppos, last, ppb))
            for j in range(n * ppb, mp)]
    # a decode row always sees its first block: one block has none dead
    assert (dead or kernel == "decode" and ppb >= mp) and len(dead) < b * mp
    nan = lambda x: x.at[jnp.asarray(dead, jnp.int32)].set(jnp.nan)
    if variant == "int8":
        k, ks = quantize_kv(k, jnp.int8)
        v, vs = quantize_kv(v, jnp.int8)
        arenas = [(k, v, dict(kw, k_scale=ks, v_scale=vs)),
                  (k, v, dict(kw, k_scale=nan(ks), v_scale=nan(vs)))]
    else:
        arenas = [(k, v, kw), (nan(k), nan(v), kw)]
    if kernel == "decode":
        qshape, run, ref = (b, hq, hd), paged_decode_attention, \
            paged_decode_attention_ref
    else:
        qshape, run, ref = (b, c, hq, hd), paged_prefill_attention, \
            paged_prefill_attention_ref
    q = jnp.asarray(rng.standard_normal(qshape), jnp.float32)
    want, got = [run(q, kk, vv, bt, *rows, pages_per_block=ppb,
                     interpret=True, **a) for kk, vv, a in arenas]
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert np.all(np.isfinite(np.asarray(g)))
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    for w, o in zip(jax.tree.leaves(want),
                    jax.tree.leaves(ref(q, k, v, bt, *rows, **arenas[0][2]))):
        np.testing.assert_allclose(np.asarray(w), np.asarray(o),
                                   rtol=1e-5, atol=1e-5)


def test_fused_prefill_matches_dense_attention_oracle():
    """A chunk at offset `start` into a contiguously-mapped single
    sequence equals dense causal attention with a query offset — the
    start-offset causal mask is exactly the chunked-prefill geometry."""
    rng = np.random.default_rng(5)
    hq, hkv, hd, page, mp, c = 4, 2, 16, 8, 4, 8
    S = mp * page
    k_full = jnp.asarray(rng.standard_normal((1, S, hkv, hd)), jnp.float32)
    v_full = jnp.asarray(rng.standard_normal((1, S, hkv, hd)), jnp.float32)
    # identity block table: page i of the arena == logical page i
    k_pages = k_full.reshape(mp, page, hkv, hd)
    v_pages = v_full.reshape(mp, page, hkv, hd)
    bt = jnp.arange(mp, dtype=jnp.int32)[None, :]
    for start in (0, 11, S - c):
        q = jnp.asarray(rng.standard_normal((1, c, hq, hd)), jnp.float32)
        got = paged_prefill_attention(q, k_pages, v_pages, bt,
                                      jnp.asarray([start], jnp.int32),
                                      jnp.asarray([c], jnp.int32),
                                      interpret=True)
        want = L.dense_attention(q, k_full[:, :start + c],
                                 v_full[:, :start + c],
                                 causal=True, q_offset=start)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


# -------------------------------------------- HLO structure (hot path)
#
# The whole point of the fused kernels: the jitted serving steps must
# not ship bulk attention intermediates through HBM.  Compiled-HLO
# shape analysis (launch/hlo_analysis conventions) over the actual
# jitted closures serving uses.

_HLO_GEOM = HLO_PROBE_GEOM


def _hlo_patterns(cfg):
    """(partials, gathered) regexes from the SHARED shape list the
    serve_throughput --json gate also sums bytes over: gather form +
    flat bitcast view of the contiguous KV copy, and the two-pass
    decode partials."""
    gather_form, flat_form, partials = (
        re.escape(s) for s in bulk_attn_shapes(cfg, **_HLO_GEOM))
    return partials, f"(?:{gather_form}|{flat_form})"


def test_jitted_paged_decode_step_ships_no_bulk_attention_buffers():
    """The fused decode step writes neither the per-page f32 partials
    (b, hkv, max_pages, group, hd) nor a gathered contiguous KV copy —
    only the (8, 128)-padded output tile leaves the kernel."""
    cfg = TINY["dense"].replace(attention_impl="flash_pallas")
    partials, gathered = _hlo_patterns(cfg)
    text = lowered_paged_hlo(cfg, "decode", **_HLO_GEOM)
    assert not re.search(partials, text)
    assert not re.search(gathered, text)
    # non-vacuity: the kernel's padded (g_pad, d_pad) output tile IS here
    assert re.search(rf"f32\[2,{cfg.num_kv_heads},8,128\]", text)
    # ... and the ORACLE formulation of the same step does gather
    oracle = lowered_paged_hlo(TINY["dense"], "decode", **_HLO_GEOM)
    assert re.search(_hlo_patterns(TINY["dense"])[1], oracle)


def test_jitted_paged_prefill_materializes_no_gathered_kv():
    """Batched prefill walks the block table inside the kernel: the
    per-layer k_l[block_table] -> (b, max_pages*page, hkv, hd) copy of
    the pre-kernel formulation must not exist in the compiled step.
    (prefill_chunk=4 != max_pages=8 keeps the query tile shape from
    colliding with the partials pattern.)"""
    cfg = TINY["dense"].replace(attention_impl="flash_pallas")
    partials, gathered = _hlo_patterns(cfg)
    text = lowered_paged_hlo(cfg, "prefill", **_HLO_GEOM)
    assert not re.search(gathered, text)
    assert not re.search(partials, text)
    oracle = lowered_paged_hlo(TINY["dense"], "prefill", **_HLO_GEOM)
    assert re.search(_hlo_patterns(TINY["dense"])[1], oracle)


# ----------------------------------------------------- engine: paged-native

def _params(cfg):
    return registry.get_family(cfg).init(jax.random.key(0), cfg)


def _run_engine(cfg, params, reqs, **kw):
    eng = ServingEngine(cfg, params, **kw)
    for r in reqs:
        eng.submit(Request(uid=r.uid, prompt=r.prompt,
                           max_new_tokens=r.max_new_tokens))
    results = eng.run()
    return eng, {r.uid: r.tokens for r in results}


def test_paged_and_contiguous_greedy_tokens_identical():
    cfg = TINY["dense"]
    params = _params(cfg)
    rng = np.random.default_rng(3)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(3, 30))
                                        ).astype(np.int32),
                    max_new_tokens=6)
            for i in range(5)]
    _, paged = _run_engine(cfg, params, reqs, max_batch=2, max_seq=64,
                           page_size=8, layout="paged")
    _, contig = _run_engine(cfg, params, reqs, max_batch=2, max_seq=64,
                            page_size=8, layout="contiguous")
    assert paged == contig


def test_chunked_prefill_matches_single_shot():
    """A long prompt prefilled 8 tokens per engine step emits the same
    tokens as the contiguous single-shot prefill."""
    cfg = TINY["dense"]
    params = _params(cfg)
    prompt = (np.arange(50, dtype=np.int32) * 5) % cfg.vocab_size
    reqs = [Request(uid=0, prompt=prompt, max_new_tokens=5)]
    _, paged = _run_engine(cfg, params, reqs, max_batch=1, max_seq=64,
                           page_size=8, prefill_chunk=8, layout="paged")
    _, contig = _run_engine(cfg, params, reqs, max_batch=1, max_seq=64,
                            layout="contiguous")
    assert paged == contig


def test_peak_kv_scales_with_tokens_in_flight():
    """Acceptance: two half-length sequences tie down <= ~55% of the
    pages the contiguous layout reserves (2 slots x max_seq)."""
    cfg = TINY["dense"]
    params = _params(cfg)
    rng = np.random.default_rng(4)
    max_seq, page = 64, 8
    # footprint 32 = max_seq/2 each (24 prompt + 8 generated)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size, 24).astype(np.int32),
                    max_new_tokens=8)
            for i in range(2)]
    eng, toks = _run_engine(cfg, params, reqs, max_batch=2, max_seq=max_seq,
                            page_size=page, layout="paged")
    assert len(toks) == 2
    contiguous_pages = 2 * max_seq // page
    peak = eng.pool.stats().peak_allocated_pages
    assert peak <= 0.55 * contiguous_pages, (peak, contiguous_pages)
    # and the byte metric agrees
    assert eng.peak_kv_bytes() == peak * eng.arena.page_bytes


def test_prefix_sharing_counted_and_correct():
    cfg = TINY["dense"]
    params = _params(cfg)
    prompt = (np.arange(24, dtype=np.int32) * 3) % cfg.vocab_size
    eng = ServingEngine(cfg, params, max_batch=3, max_seq=64, page_size=8)
    for uid in range(3):
        eng.submit(Request(uid=uid, prompt=prompt.copy(), max_new_tokens=4))
    eng.step()
    st = eng.pool.stats()
    # (24-1)//8 = 2 full pages shared by seqs 2 and 3
    assert st.shared_pages >= 2
    # without sharing: 3 seqs x (3 prompt + 1 decode-growth) = 12 pages;
    # with the 2 prompt pages shared 3 ways: 8
    assert st.allocated_pages <= 8
    res = eng.run()
    assert len(res) == 3
    assert all(r.tokens == res[0].tokens for r in res)
    assert eng.pool.stats().allocated_pages == 0


def test_cow_fork_diverges_without_corrupting_parent():
    cfg = TINY["dense"]
    params = _params(cfg)
    prompt = (np.arange(20, dtype=np.int32) * 7) % cfg.vocab_size
    # baseline: un-forked run
    _, solo = _run_engine(cfg, params,
                          [Request(uid=0, prompt=prompt, max_new_tokens=8)],
                          max_batch=1, max_seq=64, page_size=8)
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64, page_size=8)
    eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=8))
    while not any(s.generated for s in eng.slots.values()):
        eng.step()
    eng.fork(0, new_uid=1)
    st = eng.pool.stats()
    assert st.shared_pages == len(next(iter(eng.slots.values())).pages.pages)
    res = {r.uid: r.tokens for r in eng.run()}
    # greedy: parent unchanged by the fork, child identical to parent
    assert res[0] == solo[0]
    assert res[1] == res[0]
    assert eng.pool.stats().allocated_pages == 0


def test_cow_last_page_allocator_semantics():
    pool = UniMemPool(num_pages=8, page_size=4)
    seq = SequencePageTable(pool)
    seq.append_tokens(10)                    # pages A B C, C partial
    fork = seq.fork()
    assert seq.cow_last_page() is not None   # shared -> private copy
    assert seq.pages[:2] == fork.pages[:2] and seq.pages[2] != fork.pages[2]
    assert seq.cow_last_page() is None       # now exclusive: no-op
    assert fork.cow_last_page() is None      # peer became exclusive too
    seq.release(); fork.release()
    assert pool.free_pages == 8


def test_oom_backpressure_preempts_and_completes():
    """Pool too small for three concurrent sequences: lazy growth must
    preempt rather than fail, and every request still completes."""
    cfg = TINY["dense"]
    params = _params(cfg)
    reqs = [Request(uid=i, prompt=np.arange(30, dtype=np.int32),
                    max_new_tokens=8) for i in range(3)]
    eng, toks = _run_engine(cfg, params, reqs, max_batch=4, max_seq=64,
                            page_size=8, pool_pages=8, layout="paged")
    assert sorted(toks) == [0, 1, 2]
    assert all(len(t) == 8 for t in toks.values())
    assert eng.pool.stats().allocated_pages == 0


def test_cow_oom_preempts_without_double_counting_tokens():
    """COW hitting the pool limit mid-grow must preempt and retry ONLY
    the copy, not re-append the token (which would shift every later
    write position and corrupt generation)."""
    cfg = TINY["dense"]
    params = _params(cfg)
    prompt = (np.arange(20, dtype=np.int32) * 7) % cfg.vocab_size
    # footprint 24 fits EXACTLY in a 3-page pool, so the only OOM the
    # parent can hit is the COW allocation right after the fork
    _, solo = _run_engine(cfg, params,
                          [Request(uid=0, prompt=prompt, max_new_tokens=4)],
                          max_batch=1, max_seq=64, page_size=8)
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64, page_size=8,
                        pool_pages=3)
    eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=4))
    while not any(s.generated for s in eng.slots.values()):
        eng.step()
    eng.fork(0, new_uid=1)
    parent = next(s for s in eng.slots.values() if s.request.uid == 0)
    before = parent.pages.num_tokens
    eng.step()          # parent's COW OOMs -> child preempted mid-grow
    assert any(r.uid == 1 for r in eng.pending), "child was not preempted"
    # one decode step must account exactly ONE token (a combined
    # append+COW retry would re-append and shift every later write)
    assert parent.pages.num_tokens == before + 1
    res = {r.uid: r.tokens for r in eng.run()}
    assert res[0] == solo[0]         # parent positions never shifted
    assert res[1] == solo[0]         # preempted child recomputed cleanly
    assert eng.pool.stats().allocated_pages == 0


def test_oom_raises_when_one_sequence_cannot_fit():
    """No victim to preempt -> the OOM surfaces (pool genuinely too
    small for a single request's growth)."""
    cfg = TINY["dense"]
    params = _params(cfg)
    eng = ServingEngine(cfg, params, max_batch=1, max_seq=64, page_size=8,
                        pool_pages=1, layout="paged")
    eng.submit(Request(uid=0, prompt=np.arange(8, dtype=np.int32),
                       max_new_tokens=10))
    with pytest.raises(UniMemOOM):
        eng.run()


def test_paged_engine_with_pallas_kernel_matches_default():
    """End-to-end: serving through the interpret-mode Pallas kernel
    produces the same greedy tokens as the XLA-gather oracle path."""
    cfg = TINY["dense"]
    params = _params(cfg)
    prompt = (np.arange(11, dtype=np.int32) * 11) % cfg.vocab_size
    reqs = [Request(uid=0, prompt=prompt, max_new_tokens=4)]
    _, oracle = _run_engine(cfg, params, reqs, max_batch=1, max_seq=32,
                            page_size=8, layout="paged")
    cfg_k = cfg.replace(attention_impl="flash_pallas")
    _, kernel = _run_engine(cfg_k, params, reqs, max_batch=1, max_seq=32,
                            page_size=8, layout="paged")
    assert oracle == kernel


@pytest.mark.parametrize("family", ["dense", "moe", "hybrid", "vlm"])
def test_fused_kernels_serve_every_family_with_multi_page_blocks(family):
    """End-to-end across the zoo: BOTH fused kernels (decode + chunked
    prefill) with pages_per_block=2 emit the same greedy tokens as the
    XLA oracle path — prefill chunk 8 makes ragged tails cross page,
    bucket and patch/text boundaries."""
    cfg = TINY[family]
    params = registry.get_family(cfg).init(jax.random.key(0), cfg)
    rng = np.random.default_rng(sum(map(ord, family)))
    reqs = []
    for i in range(2):
        pe = (rng.standard_normal((cfg.num_patches, cfg.frontend_dim))
              .astype(np.float32) if cfg.frontend == "patch" else None)
        reqs.append(dict(uid=i, max_new_tokens=3, patch_embeds=pe,
                         prompt=rng.integers(0, cfg.vocab_size, 7 + 9 * i)
                         .astype(np.int32)))

    def run(c):
        eng = ServingEngine(c, params, max_batch=2, max_seq=64, page_size=8,
                            layout="paged", prefill_chunk=8)
        for r in reqs:
            eng.submit(Request(**r))
        return {r.uid: tuple(r.tokens) for r in eng.run()}

    fused = run(cfg.replace(attention_impl="flash_pallas",
                            attn_pages_per_block=2))
    assert fused == run(cfg)


def test_fused_prefill_ragged_tails_at_bucket_boundaries():
    """Prompt lengths straddling the bucket widths (7/8/9 with chunk 8)
    force ragged chunk tails exactly at bucket boundaries; the fused
    path must match the contiguous oracle token-for-token."""
    cfg = TINY["dense"].replace(attention_impl="flash_pallas")
    params = _params(cfg)
    reqs = [Request(uid=i, prompt=(np.arange(n, dtype=np.int32) * 5)
                    % cfg.vocab_size, max_new_tokens=4)
            for i, n in enumerate([7, 8, 9])]
    _, fused = _run_engine(cfg, params, reqs, max_batch=3, max_seq=64,
                           page_size=8, prefill_chunk=8, layout="paged")
    _, contig = _run_engine(cfg, params, reqs, max_batch=3, max_seq=64,
                            layout="contiguous")
    assert fused == contig


# ------------------------------------------- allocator lifecycle walks

def _pool_invariants(pool: UniMemPool, tables):
    """Conservation laws every reachable allocator state must satisfy."""
    # every page is either free or allocated, never both or neither
    assert len(pool._free) + len(pool._refcount) == pool.num_pages
    assert set(pool._free).isdisjoint(pool._refcount)
    # refcounts == references actually held by live tables
    held: dict[int, int] = {}
    for t in tables:
        for p in t.pages:
            held[p] = held.get(p, 0) + 1
    assert held == pool._refcount
    assert all(rc > 0 for rc in pool._refcount.values())


def test_allocator_exhaustive_state_walk_never_leaks_or_double_frees():
    """Exhaustive walk over EVERY sequence of 5 allocator ops (new /
    append+COW / fork / cow / release — the moves admission, decode
    growth, `engine.fork()`, copy-on-write and retire/preemption make)
    on a 4-page pool: refcount conservation holds in every reachable
    state, OOM never corrupts, and draining always returns the pool to
    empty.  Deterministic, no hypothesis dependency."""
    import itertools

    OPS = ("new", "append", "fork", "cow", "release")

    def apply(pool, tables, op, step):
        if op == "new":
            t = SequencePageTable(pool)
            t.append_tokens(3)                    # 2 pages, last partial
            tables.append(t)
        elif op == "append" and tables:
            t = tables[step % len(tables)]
            # engine order: grow first, then COW before the write lands
            t.append_tokens(1)
            moved = t.cow_last_page()
            if moved is not None:
                src, dst = moved
                assert src != dst and pool.is_allocated(dst)
        elif op == "fork" and tables:
            tables.append(tables[step % len(tables)].fork())
        elif op == "cow" and tables:
            tables[step % len(tables)].cow_last_page()
        elif op == "release" and tables:
            tables.pop(step % len(tables)).release()

    for seq in itertools.product(OPS, repeat=5):
        pool = UniMemPool(num_pages=4, page_size=2)
        tables: list[SequencePageTable] = []
        for step, op in enumerate(seq):
            try:
                apply(pool, tables, op, step)
            except UniMemOOM:
                pass                              # OOM must not mutate
            _pool_invariants(pool, tables)
        for t in tables:
            t.release()
        assert pool.free_pages == 4, seq          # no leak on drain
        assert not pool._refcount, seq


def test_engine_walk_fork_preempt_retire_drains_pool():
    """End-to-end allocator lifecycle through the ENGINE: prefix-shared
    admissions + a COW fork under a pool tight enough to preempt.  Every
    request completes, the pool drains to zero and the prefix cache
    holds no dangling pages at any step."""
    cfg = TINY["dense"]
    params = _params(cfg)
    prompt = (np.arange(20, dtype=np.int32) * 7) % cfg.vocab_size
    eng = ServingEngine(cfg, params, max_batch=3, max_seq=64, page_size=8,
                        pool_pages=10)
    for uid in range(2):                          # shared prefix pair
        eng.submit(Request(uid=uid, prompt=prompt.copy(), max_new_tokens=6))
    eng.submit(Request(uid=2, prompt=prompt[::-1].copy(), max_new_tokens=6))
    forked = False
    for _ in range(200):
        if not (eng.pending or eng.slots):
            break
        eng.step()
        if not forked and any(s.generated and s.request.uid == 0
                              for s in eng.slots.values()):
            if len(eng.slots) < eng.max_batch:
                eng.fork(0, new_uid=3)
                forked = True
        # prefix store must never point at freed (or re-purposed) pages
        store = eng.prefix_store
        for h in list(store._entries):
            page = store.page_of(h)
            assert eng.pool.is_allocated(page)
            assert store.hash_of(page) == h
    uids = sorted(r.uid for r in eng.results)
    assert set(uids) >= {0, 1, 2}
    assert eng.pool.stats().allocated_pages == 0
    assert len(eng.prefix_store) == 0 and not eng.prefix_store._by_page


def test_arena_null_page_is_never_allocated():
    cfg = TINY["dense"]
    arena = PagedKVArena(cfg, num_pages=4, page_size=8)
    assert arena.null_page == 4
    assert arena.k.shape[1] == 5             # pool + null slot
    pages = arena.pool.alloc(4)
    assert arena.null_page not in pages
    with pytest.raises(UniMemOOM):
        arena.pool.alloc(1)


def test_non_paged_family_falls_back_to_contiguous():
    cfg = TINY["ssm"]
    params = _params(cfg)
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=32)
    assert eng.layout == "contiguous"
    with pytest.raises(ValueError):
        ServingEngine(cfg, params, max_batch=2, max_seq=32, layout="paged")
