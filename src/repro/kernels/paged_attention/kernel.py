"""Paged flash-decoding over the UniMem arena — fused, TPU-tiled Pallas
kernel.

This generalizes `kernels/decode_attention` from a contiguous per-slot
KV cache to the pooled page arena of `serve/kv_cache.py`: K/V live in ONE
(P, page, hkv, hd) physical arena shared by every sequence, and each
sequence reaches its tokens through a (b, max_pages) block table.  That
is the paper's single pooled memory applied to serving — pages stay
RESIDENT in their arena slots (the localized DRAM arrays), the one query
is broadcast, and nothing bulkier than the final (b, hq, hd) output ever
travels back through HBM.

Kernel geometry
---------------
* **Grid (b, page_blocks)** — the batch dim is `parallel`, the page
  dim is `arbitrary`, i.e. SEQUENTIAL: it walks the block table in
  order while the online-softmax carry persists in VMEM scratch.  This
  is the fused single-pass form — no per-page partials are written to
  HBM and merged afterwards.
* **Whole-page blocks, heads inside** — each K/V block is one whole
  arena page, `(1, page, hkv, d)`: every KV head of the page arrives in
  ONE DMA, so a page's bytes cross HBM once per step, and the block's
  last two dims equal the array's (the Mosaic tiling rule — a block
  taking one head out of hkv in the second-minor dim is not a legal
  tile).  The kernel loops over the heads (static unroll), slicing each
  head's (page, d) rows out of the VMEM block.
* **VMEM carry** — running (m, l, acc) live in `scratch_shapes` VMEM
  (`(hkv, g_pad, 1)`, `(hkv, g_pad, 1)`, `(hkv, g_pad, d_pad)` f32),
  initialized at page-block 0 and folded log-sum-exp-style each block;
  the output block is written once, at the LAST page block.
* **Tiling** — the query group is padded to `g_pad` (8 f32 sublanes)
  and the head dim to `d_pad` (128 lanes), so every VMEM tile the MXU
  sees is (8k, 128k)-aligned.  q is padded host-side (tiny); K/V page
  tiles are lane-padded in-register inside the kernel so the ARENA is
  never copied.
* **pages_per_block** — each sequential grid cell DMAs `ppb` physical
  pages (one scalar-prefetched BlockSpec per page slot, so their copies
  pipeline) and reduces all of them in one (g_pad, ppb*page) score
  tile.  Block tables whose width is not a ppb multiple are padded with
  a repeat of the last column; the position mask zeroes the surplus.
* **Scalar prefetch** — the block table, positions and per-slot page
  position bases arrive via `PrefetchScalarGridSpec`, so the K/V index
  maps themselves walk the UniMem page table and the gather never
  materializes a contiguous copy of the sequence.
* **page_positions** — each block-table slot carries the ABSOLUTE kv
  position of its page's first token ((b, max_pages) int32, default
  `arange(max_pages) * page`).  A sharded arena hands every chip a
  COMPACTED table of just its resident pages with their true logical
  positions (near-memory: the walk length scales down with the mesh);
  slots past a table (or pages another shard owns) carry the
  `POS_PAD` sentinel, which the position mask kills unconditionally.
* **partials mode** — `partials=True` skips the final normalization
  and returns the raw online-softmax carry (m, l, acc) per (b, hq)
  instead of the output: the per-shard summary of the distributed
  near-memory layout.  Only these (b, hq(, hd))-sized partials ever
  cross the interconnect; `combine_splits` (or a psum-style LSE merge
  over a mesh axis) folds them into the exact global softmax.

Pages past a sequence's length may point at the arena's null slot; the
position mask zeroes their contribution, and a fully masked block
leaves the carry untouched (p is masked to 0 before it ever reaches l
or acc).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Shared log-sum-exp combine (module-level, not deferred): the fused
# kernel no longer needs it per-step, but the split/two-pass ORACLE in
# ref.py and the microbenchmarks still merge partials through it.
from repro.kernels.decode_attention.kernel import combine_splits

NEG_INF = -1e30

SUBLANE = 8      # f32 sublane tile (second-to-last dim)
LANE = 128       # lane tile (last dim)

# page-position sentinel for padded / non-resident block-table slots:
# far past any real position (positions are int32 token indices), with
# headroom so sentinel + page_size never overflows int32.
POS_PAD = 2 ** 30


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def default_page_positions(block_table, page_size: int):
    """(b, max_pages) absolute first-token position of each table slot
    for the dense (unsharded) walk: slot i holds logical page i."""
    b, mp = block_table.shape
    pos = jnp.arange(mp, dtype=jnp.int32) * page_size
    return jnp.broadcast_to(pos[None, :], (b, mp))


def _pad_block_table(block_table, page_positions, ppb: int):
    """Pad (b, max_pages) to a pages_per_block multiple — table entries
    repeat the last column (a valid slot to DMA), their page positions
    take the POS_PAD sentinel so the position mask zeroes them
    regardless of which page they name."""
    b, mp = block_table.shape
    nb = -(-mp // ppb)
    pad = nb * ppb - mp
    bt = block_table.astype(jnp.int32)
    ppos = page_positions.astype(jnp.int32)
    if pad:
        bt = jnp.concatenate(
            [bt, jnp.broadcast_to(bt[:, -1:], (b, pad))], axis=1)
        ppos = jnp.concatenate(
            [ppos, jnp.full((b, pad), POS_PAD, jnp.int32)], axis=1)
    return bt, ppos, nb


# --------------------------------------------------- shared kernel parts
#
# The decode and chunk-prefill kernels are the same machine — decode is
# the c=1 case with a simpler validity mask — so the carry machinery
# lives here ONCE and both kernel bodies compose it around their masks.

def reset_carry(m_scr, l_scr, acc_scr):
    """Zero the online-softmax VMEM carry (call at page-block 0)."""
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def load_kv_block(kv_refs, h: int, ppb: int, d: int, d_pad: int,
                  scale_refs=None):
    """Head `h`'s rows of a grid cell's ppb page blocks, concatenated
    into one (ppb*page, d_pad) K and V, lane-padding in-register (the
    arena is never copied).

    With `scale_refs` (quantized arena: K scales in slots [0, ppb), V
    scales in [ppb, 2*ppb)), the int8/fp8 tiles are dequantized here —
    f32 multiply against the (ppb*page, 1) per-token scale column while
    the tile is already in VMEM, so the dequant costs no HBM traffic."""
    k = jnp.concatenate([kv_refs[j][0, :, h, :] for j in range(ppb)], axis=0)
    v = jnp.concatenate([kv_refs[ppb + j][0, :, h, :] for j in range(ppb)],
                        axis=0)
    if scale_refs is not None:
        ks = jnp.concatenate([scale_refs[j][0][:, h:h + 1]
                              for j in range(ppb)], axis=0)
        vs = jnp.concatenate([scale_refs[ppb + j][0][:, h:h + 1]
                              for j in range(ppb)], axis=0)
        k = k.astype(jnp.float32) * ks                 # (ppb*page, d) * (.., 1)
        v = v.astype(jnp.float32) * vs
    if d_pad != d:
        k = jnp.pad(k, ((0, 0), (0, d_pad - d)))
        v = jnp.pad(v, ((0, 0), (0, d_pad - d)))
    return k, v


def attend_block(q_ref, kv_refs, scale_refs, valid, m_scr, l_scr, acc_scr,
                 *, hkv: int, ppb: int, d: int, d_pad: int):
    """One grid cell of the page walk for every KV head: scores of head
    h's query rows against its K rows of the cell's pages, folded into
    head h's carry.  `valid` ((rows, ppb*page) bool) is shared by all
    heads — positions do not depend on the head."""
    for h in range(hkv):
        k, v = load_kv_block(kv_refs, h, ppb, d, d_pad, scale_refs)
        s = jnp.dot(q_ref[0, h], k.T, preferred_element_type=jnp.float32)
        accumulate_block(s / math.sqrt(d), valid, v, h, m_scr, l_scr, acc_scr)


def accumulate_block(s, valid, v, h: int, m_scr, l_scr, acc_scr):
    """Fold one (rows, ppb*page) score block into head h's (m, l, acc)
    carry.  p is masked explicitly: a fully-invalid block keeps m at
    NEG_INF, where exp(s - m) would otherwise be exp(0) = 1 per masked
    entry — so invalid rows/blocks leave the carry at exact zero."""
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_scr[h]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new) * valid.astype(jnp.float32)
    corr = jnp.exp(m_prev - m_new)
    l_scr[h] = l_scr[h] * corr + p.sum(axis=-1, keepdims=True)
    m_scr[h] = m_new
    acc_scr[h] = acc_scr[h] * corr + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)


def emit_output(o_ref, l_scr, acc_scr):
    """Normalize the carry into the output block (call at the LAST
    page block); zero-l rows (fully masked) emit exact zeros."""
    o_ref[0] = (acc_scr[...] /
                jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def emit_partials(acc_ref, m_ref, l_ref, m_scr, l_scr, acc_scr):
    """Write the raw carry (call at the LAST page block): the per-shard
    online-softmax summary a later log-sum-exp merge normalizes."""
    acc_ref[0] = acc_scr[...].astype(acc_ref.dtype)
    m_ref[0] = m_scr[...]
    l_ref[0] = l_scr[...]


def block_kv_positions(ppos_ref, bi, pi, ppb: int, page: int, rows: int):
    """(rows, ppb*page) absolute kv position of every score column in a
    grid cell, from the scalar-prefetched per-slot position bases."""
    within = jax.lax.broadcasted_iota(jnp.int32, (rows, page), 1)
    return jnp.concatenate(
        [ppos_ref[bi, pi * ppb + j] + within for j in range(ppb)], axis=1)


def kv_block_specs(page: int, hkv: int, d: int, ppb: int):
    """One K and one V BlockSpec per page slot of a grid cell, indexed
    through the scalar-prefetched block table (first prefetch ref).
    Each block is a whole page, all KV heads included; the DMAs are
    independent and pipeline across the sequential walk."""
    def spec(j):
        return pl.BlockSpec(
            (1, page, hkv, d),
            lambda bi, pi, bt, *rest, j=j: (bt[bi, pi * ppb + j], 0, 0, 0))
    return [spec(j) for j in range(ppb)] * 2


def scale_block_specs(page: int, hkv: int, ppb: int):
    """BlockSpecs of the per-page scale tiles ((P, page, hkv) arrays) a
    quantized arena streams beside its K/V pages — same block-table
    walk, one whole (1, page, hkv) page per page slot."""
    def spec(j):
        return pl.BlockSpec(
            (1, page, hkv),
            lambda bi, pi, bt, *rest, j=j: (bt[bi, pi * ppb + j], 0, 0))
    return [spec(j) for j in range(ppb)] * 2


def carry_scratch(hkv: int, rows: int, d_pad: int):
    """VMEM scratch of the per-head online-softmax carry."""
    return [pltpu.VMEM((hkv, rows, 1), jnp.float32),       # running max
            pltpu.VMEM((hkv, rows, 1), jnp.float32),       # running normalizer
            pltpu.VMEM((hkv, rows, d_pad), jnp.float32)]   # running accumulator


def carry_outputs(partials: bool, b: int, hkv: int, rows: int, d_pad: int,
                  dtype):
    """(out_shape, out_specs) of the walk: the normalized (b, hkv, rows,
    d_pad) output, or with `partials` the raw (acc, m, l) carry in f32."""
    def spec(last):
        return pl.BlockSpec((1, hkv, rows, last),
                            lambda bi, pi, *pref: (bi, 0, 0, 0))
    if partials:
        return ([jax.ShapeDtypeStruct((b, hkv, rows, d_pad), jnp.float32),
                 jax.ShapeDtypeStruct((b, hkv, rows, 1), jnp.float32),
                 jax.ShapeDtypeStruct((b, hkv, rows, 1), jnp.float32)],
                [spec(d_pad), spec(1), spec(1)])
    return ([jax.ShapeDtypeStruct((b, hkv, rows, d_pad), dtype)],
            [spec(d_pad)])


# batch cells are independent; the page walk carries VMEM state and must
# stay in order ("arbitrary")
COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def _paged_kernel(bt_ref, pos_ref, ppos_ref, q_ref, *refs,
                  page_size: int, ppb: int, nb: int, hkv: int, d: int,
                  d_pad: int, partials: bool, nscale: int = 0):
    kv_refs = refs[:2 * ppb]
    scale_refs = refs[2 * ppb:2 * ppb + nscale] if nscale else None
    rest = refs[2 * ppb + nscale:]
    if partials:
        acc_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    bi = pl.program_id(0)
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        reset_carry(m_scr, l_scr, acc_scr)

    kv_pos = block_kv_positions(ppos_ref, bi, pi, ppb, page_size,
                                q_ref.shape[2])            # (g_pad, ppb*page)
    attend_block(q_ref, kv_refs, scale_refs, kv_pos <= pos_ref[bi],
                 m_scr, l_scr, acc_scr, hkv=hkv, ppb=ppb, d=d, d_pad=d_pad)

    @pl.when(pi == nb - 1)
    def _emit():
        if partials:
            emit_partials(acc_ref, m_ref, l_ref, m_scr, l_scr, acc_scr)
        else:
            emit_output(o_ref, l_scr, acc_scr)


def paged_decode_attention_pallas(q, k_pages, v_pages, block_table,
                                  positions, *, pages_per_block: int = 1,
                                  page_positions=None, partials: bool = False,
                                  k_scale=None, v_scale=None,
                                  interpret: bool = False):
    """q: (b, hq, d); k_pages/v_pages: (P, page, hkv, d) physical arena
    for ONE layer; block_table: (b, max_pages) int32 physical page ids
    (entries past the sequence may be any valid slot, e.g. the null
    page); positions: (b,) inclusive newest token index;
    page_positions: optional (b, max_pages) absolute first-token
    position per table slot (default: slot i == logical page i — a
    sharded walk passes its resident pages' true positions, POS_PAD for
    holes); k_scale/v_scale: optional (P, page, hkv) f32 per-token
    scales of a quantized (int8/fp8) arena — page tiles are dequantized
    in-register inside the page loop, the softmax math stays f32.
    Returns (b, hq, d) directly — no per-page partials touch HBM — or,
    with `partials=True`, the raw carry as (m (b, hq), l (b, hq),
    acc (b, hq, d)) f32 for a cross-shard log-sum-exp merge."""
    b, hq, d = q.shape
    page = k_pages.shape[1]
    hkv = k_pages.shape[2]
    group = hq // hkv
    mp = block_table.shape[1]
    ppb = max(1, min(pages_per_block, mp))
    if page_positions is None:
        page_positions = default_page_positions(block_table, page)
    bt, ppos, nb = _pad_block_table(block_table, page_positions, ppb)

    g_pad = _round_up(max(group, SUBLANE), SUBLANE)
    d_pad = _round_up(d, LANE)
    qg = q.reshape(b, hkv, group, d)
    if (g_pad, d_pad) != (group, d):
        qg = jnp.pad(qg, ((0, 0), (0, 0),
                          (0, g_pad - group), (0, d_pad - d)))

    out_shape, out_specs = carry_outputs(partials, b, hkv, g_pad, d_pad,
                                         q.dtype)
    quant = k_scale is not None
    nscale = 2 * ppb if quant else 0
    scale_args = ((*([k_scale] * ppb), *([v_scale] * ppb)) if quant else ())

    # index maps take the grid indices first, then the scalar-prefetch refs
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nb),
        in_specs=[pl.BlockSpec((1, hkv, g_pad, d_pad),
                               lambda bi, pi, *pref: (bi, 0, 0, 0))]
                 + kv_block_specs(page, hkv, d, ppb)
                 + (scale_block_specs(page, hkv, ppb) if quant else []),
        out_specs=out_specs,
        scratch_shapes=carry_scratch(hkv, g_pad, d_pad),
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, page_size=page, ppb=ppb, nb=nb,
                          hkv=hkv, d=d, d_pad=d_pad, partials=partials,
                          nscale=nscale),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(bt, positions.astype(jnp.int32), ppos, qg,
      *([k_pages] * ppb), *([v_pages] * ppb), *scale_args)
    if partials:
        acc, m, l = out
        return (m[:, :, :group, 0].reshape(b, hq),
                l[:, :, :group, 0].reshape(b, hq),
                acc[:, :, :group, :d].reshape(b, hq, d))
    return out[0][:, :, :group, :d].reshape(b, hq, d)


def combine_pages(m, l, acc, b: int, hq: int, d: int, out_dtype):
    """Log-sum-exp merge of per-page partials -> (b, hq, d).  The fused
    kernel no longer produces partials; this stays as the merge step of
    the two-pass ORACLE (`ref.paged_decode_attention_split_ref`) the
    kernel is tested against — a page is just a split whose offset came
    from the block table."""
    hkv, mp = m.shape[1], m.shape[2]
    group = hq // hkv
    m2 = m.reshape(b * hkv, mp, group)
    l2 = l.reshape(b * hkv, mp, group)
    a2 = acc.reshape(b * hkv, mp, group, d)
    return combine_splits(m2, l2, a2, b, hq, d, out_dtype)
