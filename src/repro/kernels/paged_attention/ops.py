"""Jit'd wrapper for fused paged flash-decoding (the Pallas interpreter
on the CPU backend, the compiled kernel elsewhere)."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import interpret_default
from repro.kernels.paged_attention import kernel as K


@partial(jax.jit, static_argnames=("pages_per_block", "partials",
                                   "interpret"))
def paged_decode_attention(q, k_pages, v_pages, block_table, positions,
                           pages_per_block=1, page_positions=None,
                           partials=False, k_scale=None, v_scale=None,
                           interpret=None):
    """q: (b, hq, d); k_pages/v_pages: (P, page, hkv, d) one layer's
    arena; block_table: (b, max_pages); positions: (b,) inclusive newest
    index.  Single pass — the kernel carries the online softmax in VMEM
    and emits (b, hq, d) directly; `pages_per_block` physical pages are
    reduced per sequential grid cell.

    `page_positions` (optional (b, max_pages) int32) gives each table
    slot's absolute first-token position — a sharded arena walks ONLY
    its resident pages by passing a compacted table with their true
    logical positions (K.POS_PAD for holes).  `partials=True` exposes
    the online-softmax carry as (m (b, hq), l (b, hq), acc (b, hq, d))
    f32 — the summary-sized per-shard state a log-sum-exp merge
    (`distribution.collectives.combine_shard_partials`) folds into the
    exact global attention output.

    `k_scale`/`v_scale` (optional (P, page, hkv) f32) are the per-token
    scale banks of a quantized (int8/fp8) arena — the kernel dequantizes
    each page tile in-register inside the page loop."""
    interpret = interpret_default(interpret)
    return K.paged_decode_attention_pallas(
        q, k_pages, v_pages, block_table, positions,
        pages_per_block=pages_per_block, page_positions=page_positions,
        partials=partials, k_scale=k_scale, v_scale=v_scale,
        interpret=interpret)
