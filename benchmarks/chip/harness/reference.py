"""The plain reference: the published decoder's forward pass, written
here from its description and nothing of the program.

Llama-style block (InternLM2 and Yi share it): RMSNorm, grouped-query
attention with rotary embeddings (rotate-half form, inverse frequencies
theta^(-2i/head_dim)), causal softmax scaled by 1/sqrt(head_dim),
SwiGLU MLP, a final RMSNorm and an untied output head. It runs in
float32 at the highest matmul precision on the same bf16-valued
weights, one layer at a time (a scan casts each layer to float32 as it
goes) and queries in blocks, so that it fits beside the weights.

`precision="fp8"` is the control: every matmul input rounded to
float8 e4m3 (per row of activations, per output column of weights),
the step below the bf16 the configurations state."""
from __future__ import annotations

import math
from functools import partial

import numpy as np
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


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (T, h, hd); rotate-half rotary embedding at positions pos (T,)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / x.shape[-1])
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, D, fp8: bool, block: int):
    """Causal GQA attention. q (T, hq, hd); k, v (T, hkv, hd)."""
    T = q.shape[0]
    g = D["hq"] // D["hkv"]
    scale = 1.0 / math.sqrt(D["hd"])
    if fp8:
        q, k, v = _round8(q, -1), _round8(k, -1), _round8(v, 0)
    qb = q.reshape(T // block, block, D["hkv"], g, D["hd"])
    kpos = jnp.arange(T)

    def one(args):
        i, qi = args
        s = jnp.einsum("qhgd,khd->hgqk", qi, k, precision=HI) * scale
        qpos = i * block + jnp.arange(block)
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if fp8:
            p = _round8(p, -1)
        return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI)

    o = jax.lax.map(one, (jnp.arange(T // block), qb))
    return o.reshape(T, D["hq"] * D["hd"])


def _forward(params, tokens, read, D, fp8: bool, block: int):
    """Logits (K, V) at positions `read` of the sequence `tokens` (T,)."""
    f32 = jnp.float32
    T = tokens.shape[0]
    pos = jnp.arange(T)
    x = jnp.take(params["embed"], tokens, axis=0).astype(f32)

    def layer(x, p):
        p = jax.tree.map(lambda a: a.astype(f32), p)
        h = _rms(x, p["ln1"], D["eps"])
        q = _mm(h, p["attn"]["wq"], fp8).reshape(T, D["hq"], D["hd"])
        k = _mm(h, p["attn"]["wk"], fp8).reshape(T, D["hkv"], D["hd"])
        v = _mm(h, p["attn"]["wv"], fp8).reshape(T, D["hkv"], D["hd"])
        q, k = _rope(q, pos, D["theta"]), _rope(k, pos, D["theta"])
        x = x + _mm(_attention(q, k, v, D, fp8, block), p["attn"]["wo"], fp8)
        h = _rms(x, p["ln2"], D["eps"])
        m = p["mlp"]
        a = jax.nn.silu(_mm(h, m["wg"], fp8)) * _mm(h, m["wi"], fp8)
        return x + _mm(a, m["wo"], fp8), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    h = _rms(x[read], params["ln_f"].astype(f32), D["eps"])
    return _mm(h, params["head"].astype(f32), fp8)


@partial(jax.jit, static_argnames=("D", "block", "control"))
def _gaps(params, tokens, read, served, valid, *, D, block, control):
    """Per read position: how far the served token's reference logit
    lies below the reference's best; with `control`, also the same gap
    of the token the fp8 forward puts first."""
    D = dict(D)
    z = _forward(params, tokens, read, D, False, block)
    best = z.max(-1)
    gap = best - jnp.take_along_axis(z, served[:, None], 1)[:, 0]
    gap = jnp.where(valid, gap, 0.0)
    if not control:
        return gap, gap
    z8 = _forward(params, tokens, read, D, True, block)
    t8 = jnp.argmax(z8, -1)
    gap8 = best - jnp.take_along_axis(z, t8[:, None], 1)[:, 0]
    return gap, jnp.where(valid, gap8, 0.0)


def padded_length(max_seq: int, block: int) -> int:
    return -(-max_seq // block) * block


def served_gaps(params, D: dict, pairs, *, max_seq: int, max_new: int,
                block: int = 512, control: bool = False):
    """For each (prompt, served tokens) pair, the per-token gaps (and,
    with `control`, the fp8 control's gaps at the same positions).

    The reference is fed prompt + served[:-1] (teacher forcing) and read
    at the positions that produced each served token. Every call has
    the same shapes (the cell's max_seq, padded to the query block, and
    its largest output), so it compiles once per cell."""
    block = min(block, max_seq)
    T = padded_length(max_seq, block)
    Dk = tuple(sorted(D.items()))
    out = []
    for prompt, served in pairs:
        served = np.asarray(served, np.int32)
        n, k = len(prompt), len(served)
        seq = np.zeros((T,), np.int32)
        seq[:n + k - 1] = np.concatenate([prompt, served[:-1]])
        read = np.zeros((max_new,), np.int32)
        read[:k] = np.arange(n - 1, n - 1 + k)
        tok = np.zeros((max_new,), np.int32)
        tok[:k] = served
        valid = np.arange(max_new) < k
        g, g8 = _gaps(params, seq, read, tok, valid, D=Dk, block=block,
                      control=control)
        out.append((np.asarray(g)[:k], np.asarray(g8)[:k]))
    return out
