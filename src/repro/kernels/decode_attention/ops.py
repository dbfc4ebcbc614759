"""Jit'd wrapper for split-KV decode attention (XLA fallback off-TPU)."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import interpret_default
from repro.kernels.decode_attention import kernel as K


@partial(jax.jit, static_argnames=("kv_splits", "interpret"))
def decode_attention(q, k_cache, v_cache, positions, kv_splits=8,
                     interpret=None):
    """q: (b, hq, d); caches (b, S, hkv, d); positions (b,) inclusive
    newest index.  Returns (b, hq, d)."""
    interpret = interpret_default(interpret)
    b, hq, d = q.shape
    m, l, acc = K.decode_attention_pallas(
        q, k_cache, v_cache, positions, kv_splits=kv_splits,
        interpret=interpret)
    return K.combine_splits(m, l, acc, b, hq, d, q.dtype)
