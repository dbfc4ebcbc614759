"""What every block shares about weights: the dtypes a configuration
may state, the key drawn from a seed, and the check that a block's
weights have exactly the program's parameter layout.

The weights are the benchmark's input, not the program's: each block
(`blocks/<name>.py`) draws them on the device, in one jitted call from
the seed, in the layout and the dtype the program serves."""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def seed_key(seed: int):
    """A JAX key from a seed of any size (jax.random.key keeps only the
    low 32 bits of a large int)."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words)


def check_layout(params, cfg) -> None:
    """The weights must have exactly the program's parameter shapes."""
    from repro.models import registry
    fam = registry.get_family(cfg)
    want = jax.eval_shape(lambda k: fam.init(k, cfg), jax.random.key(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if got != want:
        raise ValueError(f"weights do not match the program's layout:\n"
                         f"made {got}\nprogram {want}")
