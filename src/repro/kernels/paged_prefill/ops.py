"""Jit'd wrapper for fused paged chunk-prefill attention (the Pallas
interpreter on the CPU backend, the compiled kernel elsewhere)."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import interpret_default
from repro.kernels.paged_prefill import kernel as K


@partial(jax.jit, static_argnames=("pages_per_block", "partials",
                                   "interpret"))
def paged_prefill_attention(q, k_pages, v_pages, block_table, start,
                            chunk_len, pages_per_block=1,
                            page_positions=None, partials=False,
                            k_scale=None, v_scale=None, interpret=None):
    """q: (b, c, hq, d) chunk queries; k_pages/v_pages: (P, page, hkv, d)
    one layer's arena; block_table: (b, max_pages); start/chunk_len: (b,)
    chunk geometry.  Returns (b, c, hq, d); rows past chunk_len are
    exact zeros.

    `page_positions` (optional (b, max_pages) int32) carries each table
    slot's absolute first-token position so a shard can walk a compacted
    table of just its resident pages; `partials=True` returns the
    online-softmax carry (m (b, c, hq), l (b, c, hq), acc (b, c, hq, d))
    f32 for the cross-shard log-sum-exp merge instead of the normalized
    output.

    `k_scale`/`v_scale` (optional (P, page, hkv) f32) are a quantized
    arena's per-token scale banks — dequantized in-register inside the
    kernel's page loop."""
    interpret = interpret_default(interpret)
    return K.paged_prefill_attention_pallas(
        q, k_pages, v_pages, block_table, start, chunk_len,
        pages_per_block=pages_per_block, page_positions=page_positions,
        partials=partials, k_scale=k_scale, v_scale=v_scale,
        interpret=interpret)
