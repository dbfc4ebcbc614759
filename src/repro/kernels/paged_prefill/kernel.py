"""Paged chunk-prefill attention — fused, TPU-tiled Pallas kernel.

The batched-prefill analogue of `kernels/paged_attention`: a ragged
(b, c) prompt chunk attends causally against everything already written
into each row's pages (shared prefix included).  The pre-kernel
formulation gathered a full contiguous KV copy per layer
(`k_l[block_table] -> (b, max_pages*page, hkv, hd)`) and ran a dense
masked softmax over it; here the chunk queries walk the
scalar-prefetched block table directly — pages stay RESIDENT in the
arena, and only the (b, c, hq, hd) chunk output leaves the kernel.

Kernel geometry
---------------
* **Grid (b, page_blocks)** — b is `parallel`; the page-block dim is
  `arbitrary` (SEQUENTIAL), walking each row's block table in order
  while the online-softmax carry persists in VMEM scratch.  As in the
  decode kernel, each K/V block is a whole page with every KV head
  (one DMA per page per step), and the kernel loops over the heads.
* **Query tile** — per KV head, the whole chunk rides in one
  (R, d_pad) VMEM tile with chunk rows packed DENSELY along sublanes:
  row r of the score tile is chunk position r // group, query-group
  member r % group, and R = c*group rounds up to the 8-sublane f32
  tile ONCE for the whole chunk (not per row — a group-2 chunk costs 2
  rows per position, not 8); the head dim pads to `d_pad` (128 lanes).
* **VMEM carry** — running (m, l, acc) scratch of shapes
  `(hkv, R, 1)`, `(hkv, R, 1)`, `(hkv, R, d_pad)` f32, initialized at
  page-block 0; the output block is written once, at the LAST block.
* **Masking** — `start`-offset causal (kv_pos <= start[b] + chunk_row)
  AND ragged `chunk_len` (rows past chunk_len[b] are fully masked and
  emit exact zeros — inert bucket-tail rows are deterministic, never
  garbage).
* **pages_per_block** — as in the decode kernel: `ppb` physical pages
  per sequential cell via one scalar-prefetched BlockSpec per page
  slot; non-multiple table widths pad with the last column (masked).
* **Live range** — as in the decode kernel, each row walks only up to
  the block holding its last query position, start + chunk_len - 1 (a
  row with chunk_len 0 walks none): blocks past it are neither fetched
  nor computed, and the row still emits (zeros where it has none).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_attention.kernel import (
    COMPILER_PARAMS, LANE, SUBLANE, _pad_block_table, _round_up,
    attend_block, block_kv_positions, carry_outputs, carry_scratch,
    default_page_positions, emit_output, emit_partials, kv_block_specs,
    live_blocks, reset_carry, scale_block_specs)


def _prefill_kernel(bt_ref, nlive_ref, start_ref, clen_ref, ppos_ref, q_ref,
                    *refs, page_size: int, ppb: int, nb: int, hkv: int,
                    group: int, d: int, d_pad: int, partials: bool,
                    nscale: int = 0):
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

    # the decode kernel's machine with the chunk mask: start-offset
    # causal over absolute positions AND ragged chunk_len row validity
    # (tail rows and the sublane-padding rows past c*group get
    # ci >= chunk_len and end up exact zeros via the masked carry)
    @pl.when(pi < nlive_ref[bi])
    def _attend():
        kv_pos = block_kv_positions(ppos_ref, bi, pi, ppb, page_size,
                                    q_ref.shape[2])        # (R, ppb*page)
        ci = jax.lax.broadcasted_iota(jnp.int32, kv_pos.shape, 0) // group
        q_pos = start_ref[bi] + ci                         # absolute position
        valid = (kv_pos <= q_pos) & (ci < clen_ref[bi])
        attend_block(q_ref, kv_refs, scale_refs, valid, m_scr, l_scr,
                     acc_scr, hkv=hkv, ppb=ppb, d=d, d_pad=d_pad)

    @pl.when(pi == nb - 1)
    def _emit():
        if partials:
            emit_partials(acc_ref, m_ref, l_ref, m_scr, l_scr, acc_scr)
        else:
            emit_output(o_ref, l_scr, acc_scr)


def paged_prefill_attention_pallas(q, k_pages, v_pages, block_table, start,
                                   chunk_len, *, pages_per_block: int = 1,
                                   page_positions=None, partials: bool = False,
                                   k_scale=None, v_scale=None,
                                   interpret: bool = False):
    """q: (b, c, hq, d) chunk queries at absolute positions
    start[i]..start[i]+c-1; k_pages/v_pages: (P, page, hkv, d) ONE
    layer's arena (the chunk's own K/V already written); block_table:
    (b, max_pages) int32; chunk_len: (b,) valid rows per chunk (rows
    past it emit zeros).  Returns (b, c, hq, d) — the gathered
    (b, max_pages*page, hkv, hd) KV copy never exists.

    `page_positions` maps table slots to absolute positions (sharded
    walks pass a compacted table of resident pages, POS_PAD for holes);
    `partials=True` returns the carry (m (b, c, hq), l (b, c, hq),
    acc (b, c, hq, d)) f32 for the cross-shard log-sum-exp merge;
    `k_scale`/`v_scale` ((P, page, hkv) f32) dequantize an int8/fp8
    arena's page tiles in-register inside the page loop."""
    b, c, hq, d = q.shape
    page = k_pages.shape[1]
    hkv = k_pages.shape[2]
    group = hq // hkv
    mp = block_table.shape[1]
    ppb = max(1, min(pages_per_block, mp))
    if page_positions is None:
        page_positions = default_page_positions(block_table, page)
    bt, ppos, nb = _pad_block_table(block_table, page_positions, ppb)
    start = start.astype(jnp.int32)
    chunk_len = chunk_len.astype(jnp.int32)
    nlive = live_blocks(ppos, jnp.where(chunk_len > 0,
                                        start + chunk_len - 1, -1), ppb)

    d_pad = _round_up(d, LANE)
    qg = jnp.moveaxis(q.reshape(b, c, hkv, group, d), 2, 1)
    if d_pad != d:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, 0), (0, d_pad - d)))
    # dense row packing: row ci*group + gi; ONE sublane round-up for
    # the whole chunk (padding rows mask out via ci >= chunk_len)
    rows = c * group
    R = _round_up(rows, SUBLANE)
    qg = qg.reshape(b, hkv, rows, d_pad)
    if R != rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, R - rows), (0, 0)))

    out_shape, out_specs = carry_outputs(partials, b, hkv, R, d_pad, q.dtype)
    quant = k_scale is not None
    nscale = 2 * ppb if quant else 0
    scale_args = ((*([k_scale] * ppb), *([v_scale] * ppb)) if quant else ())

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b, nb),
        in_specs=[pl.BlockSpec((1, hkv, R, d_pad),
                               lambda bi, pi, *pref: (bi, 0, 0, 0))]
                 + kv_block_specs(page, hkv, d, ppb)
                 + (scale_block_specs(page, hkv, ppb) if quant else []),
        out_specs=out_specs,
        scratch_shapes=carry_scratch(hkv, R, d_pad),
    )
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, page_size=page, ppb=ppb, nb=nb,
                          hkv=hkv, group=group, d=d, d_pad=d_pad,
                          partials=partials, nscale=nscale),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(bt, nlive, start, chunk_len, ppos, qg,
      *([k_pages] * ppb), *([v_pages] * ppb), *scale_args)

    def unpack(x, dd):
        x = x[:, :, :rows, :dd].reshape(b, hkv, c, group, dd)
        return jnp.moveaxis(x, 1, 2).reshape(b, c, hq, dd)

    if partials:
        acc, m, l = out
        return (unpack(m, 1)[..., 0], unpack(l, 1)[..., 0], unpack(acc, d))
    return unpack(out[0], d)
