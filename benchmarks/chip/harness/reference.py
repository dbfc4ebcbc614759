"""What every block's plain reference shares: float32 matmuls at the
highest precision, and the fp8 control's rounding of their inputs
(float8 e4m3, per row of activations, per output column of weights),
the step below the bf16 the configurations state. Each block's forward
pass is in its own file (`blocks/<name>.py`) and imports nothing of the
program."""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _round8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, fp8: bool):
    """a (..., k) @ b (k, n) in float32."""
    if fp8:
        a, b = _round8(a, -1), _round8(b, 0)
    return jnp.matmul(a, b, precision=HI)
