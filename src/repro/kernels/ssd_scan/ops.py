"""Jit'd wrapper for the SSD intra-chunk kernel (XLA fallback off-TPU)."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import interpret_default
from repro.kernels.ssd_scan import kernel as K


@partial(jax.jit, static_argnames=("interpret",))
def ssd_intra_chunk(x, dt, A, B, C, interpret=None):
    interpret = interpret_default(interpret)
    return K.ssd_intra_chunk_pallas(x, dt, A, B, C, interpret=interpret)
