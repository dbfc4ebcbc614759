# Pallas TPU kernels for the compute hot spots the paper optimizes.
# Each subpackage: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
# wrapper with XLA fallback), ref.py (pure-jnp oracle for tests).
#
#   ws_matmul/        weight-stationary blocked matmul (the paper's dataflow)
#   flash_attention/  online-softmax attention (prefill hot spot)
#   decode_attention/ split-KV flash-decoding (resident KV, broadcast query)
#   paged_attention/  flash-decoding through a UniMem block table (paged KV)
#   ssd_scan/         Mamba-2 SSD intra-chunk dual form
#   grouped_matmul/   per-expert MoE matmul (vector-unit sparsity)
#   paged_prefill/    chunk prefill through the same block table
#
# Every ops.py runs its kernel in the Pallas interpreter on the CPU
# backend only, and compiled everywhere else (`interpret_default`).

import jax


def interpret_default(interpret: bool | None) -> bool:
    """An explicit `interpret` wins; None means the interpreter on the
    CPU backend and the compiled kernel on any other."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret
