"""Jit'd wrappers for the weight-stationary matmul kernels.

On the CPU backend the wrappers run the kernel body in the Pallas
interpreter; elsewhere the compiled kernel runs.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import interpret_default
from repro.kernels.ws_matmul import kernel as K
from repro.kernels.ws_matmul.ref import matmul_ref


@partial(jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret"))
def ws_matmul(x, w, block_m=K.DEF_BM, block_n=K.DEF_BN, block_k=K.DEF_BK,
              interpret=None):
    """Weight-stationary matmul; interpret defaults to True on the CPU."""
    interpret = interpret_default(interpret)
    return K.ws_matmul_pallas(x, w, block_m=block_m, block_n=block_n,
                              block_k=block_k, interpret=interpret)


@partial(jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret"))
def os_matmul(x, w, block_m=K.DEF_BM, block_n=K.DEF_BN, block_k=K.DEF_BK,
              interpret=None):
    """Output-stationary ablation twin."""
    interpret = interpret_default(interpret)
    return K.os_matmul_pallas(x, w, block_m=block_m, block_n=block_n,
                              block_k=block_k, interpret=interpret)
