"""Distributed-optimization collectives: gradient compression + bucketing
+ the near-memory attention merge.

`compressed_tree_psum` replaces XLA's automatic cross-pod gradient
all-reduce with an int8-on-the-wire ring all-reduce (shard_map over the
"pod" axis, data/model axes left on auto).  Error feedback buffers keep
the quantization bias from accumulating.  On a 2-pod mesh this cuts
cross-DCI gradient bytes 4x (bf16/f32 -> int8 + one f32 scale per tensor).

`bucket_psum` groups small tensors into flat buckets before reduction —
fewer, larger collectives (latency hiding at scale).

`combine_shard_partials` is the serving-side summary merge (DESIGN.md
§2): each chip of a `mem`-sharded page arena computes attention over its
RESIDENT pages only and ships its online-softmax carry (m, l, acc) —
(batch, heads(, head_dim))-sized summaries, never pages — across the
interconnect, where the battle-tested `combine_splits` log-sum-exp
reduction folds them into the exact global softmax.
"""
from __future__ import annotations

import math
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.decode_attention.kernel import combine_splits


# ------------------------------------------------- near-memory LSE merge

def combine_shard_partials(m, l, acc, axis: str, out_dtype):
    """Merge per-shard online-softmax partials across a bound mesh axis.

    m, l: (..., hq) f32; acc: (..., hq, d) f32 — the partials-mode
    output of the paged attention kernels over each shard's resident
    pages (any number of leading batch/chunk dims).  All-gathers ONLY
    these summary-sized tensors over `axis` (inside shard_map) and
    reduces them with `combine_splits` — the same log-sum-exp algebra
    the split-KV decode kernel has always used; a shard is just a split
    whose offsets came from the page→shard mapping.  A shard with no
    resident pages for a row contributes (m=-inf, l=0, acc=0), the
    merge's identity.  Returns (..., hq, d) in `out_dtype`, replicated
    across the axis."""
    hq, d = m.shape[-1], acc.shape[-1]
    lead = m.shape[:-1]
    B = math.prod(lead) if lead else 1
    mg = jax.lax.all_gather(m.reshape(B, hq), axis, axis=1)      # (B, n, hq)
    lg = jax.lax.all_gather(l.reshape(B, hq), axis, axis=1)
    ag = jax.lax.all_gather(acc.reshape(B, hq, d), axis, axis=1)  # (B,n,hq,d)
    o = combine_splits(mg, lg, ag, B, hq, d, out_dtype)           # (B, hq, d)
    return o.reshape(*lead, hq, d)


# ------------------------------------------------------------ quantization

def quantize_int8(x):
    """Symmetric per-tensor int8 quantization.  Returns (q, scale)."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


def ring_allreduce_int8(x, axis: str):
    """All-reduce with int8 wire format over `axis` (inside shard_map).

    Each hop passes the ORIGINAL quantized block along the ring and
    accumulates the dequantized value — n-1 hops, int8 bytes on the wire.
    """
    n = jax.lax.axis_size(axis)
    q, scale = quantize_int8(x)
    acc = dequantize_int8(q, scale)
    perm = [(i, (i + 1) % n) for i in range(n)]
    for _ in range(n - 1):
        q = jax.lax.ppermute(q, axis, perm)
        scale = jax.lax.ppermute(scale, axis, perm)
        acc = acc + dequantize_int8(q, scale)
    return acc


def compressed_tree_psum(grads, mesh, axis: str = "pod", error_feedback=None):
    """int8 ring all-reduce of a gradient pytree across `axis`.

    grads are assumed NOT yet reduced over `axis` (use inside a shard_map
    region or with per-pod partial grads).  Returns (reduced_grads,
    new_error_feedback).
    """
    if error_feedback is None:
        error_feedback = jax.tree.map(
            lambda g: jnp.zeros(g.shape, jnp.float32), grads)

    def one(g, err):
        def body(gl, el):
            x = gl.astype(jnp.float32) + el
            q, scale = quantize_int8(x)
            reduced = ring_allreduce_int8(x, axis) / jax.lax.axis_size(axis)
            new_err = x - dequantize_int8(q, scale)
            return reduced.astype(gl.dtype), new_err

        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P()), out_specs=(P(), P()),
            axis_names=frozenset({axis}), check_vma=False,
        )
        return fn(g, err)

    out = jax.tree.map(one, grads, error_feedback)
    is_pair = lambda x: isinstance(x, tuple)
    red = jax.tree.map(lambda o: o[0], out, is_leaf=is_pair)
    new_err = jax.tree.map(lambda o: o[1], out, is_leaf=is_pair)
    return red, new_err


# ---------------------------------------------------------------- bucketing

def bucket_psum(grads, axis_name: str, bucket_bytes: int = 4 << 20):
    """Flatten leaves into ~bucket_bytes buckets and psum each bucket.
    For use INSIDE shard_map/pmap regions (axis_name must be bound)."""
    leaves, treedef = jax.tree.flatten(grads)
    sizes = [int(np.prod(l.shape)) for l in leaves]
    flat = [l.reshape(-1).astype(jnp.float32) for l in leaves]

    buckets, cur, cur_bytes = [], [], 0
    for f in flat:
        cur.append(f)
        cur_bytes += f.size * 4
        if cur_bytes >= bucket_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
    if cur:
        buckets.append(cur)

    reduced_flat = []
    for bucket in buckets:
        cat = jnp.concatenate(bucket) if len(bucket) > 1 else bucket[0]
        red = jax.lax.psum(cat, axis_name)
        off = 0
        for f in bucket:
            reduced_flat.append(red[off:off + f.size])
            off += f.size

    out = [r.reshape(l.shape).astype(l.dtype)
           for r, l in zip(reduced_flat, leaves)]
    return jax.tree.unflatten(treedef, out)
