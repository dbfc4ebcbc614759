"""Jit'd wrapper for the flash-attention kernel (XLA fallback off-TPU)."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import interpret_default
from repro.kernels.flash_attention import kernel as K


@partial(jax.jit, static_argnames=("causal", "block_q", "block_kv",
                                   "q_offset", "interpret"))
def flash_attention(q, k, v, causal=True, block_q=K.DEF_BQ, block_kv=K.DEF_BKV,
                    q_offset=0, interpret=None):
    interpret = interpret_default(interpret)
    return K.flash_attention_pallas(q, k, v, causal=causal, block_q=block_q,
                                    block_kv=block_kv, q_offset=q_offset,
                                    interpret=interpret)
