"""Jit'd wrapper for the grouped matmul kernel (XLA fallback off-TPU)."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import interpret_default
from repro.kernels.grouped_matmul import kernel as K


@partial(jax.jit, static_argnames=("block_c", "block_f", "block_k", "interpret"))
def grouped_matmul(x, w, block_c=K.DEF_BC, block_f=K.DEF_BF, block_k=K.DEF_BK,
                   interpret=None):
    interpret = interpret_default(interpret)
    return K.grouped_matmul_pallas(x, w, block_c=block_c, block_f=block_f,
                                   block_k=block_k, interpret=interpret)
