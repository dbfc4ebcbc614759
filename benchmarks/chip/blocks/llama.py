"""The Llama block: InternLM2 and Yi share it.

A block is one architecture, as the benchmark sees it: how a
configuration file maps onto the program's ModelConfig, the weights
drawn from the seed, the plain reference that decides `correct` and its
fp8 control, and the operations and bytes the algorithm needs. A
configuration names its block (`"block": "llama"`), and the harness
finds this file by that name (harness/spec.py).

Weights: drawn on the device in one jitted call from the seed, in the
layout the program serves (run.py checks it against the program's own
shapes) and in bf16. The float32 reference reads the same arrays.

Reference: the published decoder's forward pass, written here from its
description and nothing of the program. RMSNorm, grouped-query
attention with rotary embeddings (rotate-half form, inverse frequencies
theta^(-2i/head_dim)), causal softmax scaled by 1/sqrt(head_dim),
SwiGLU MLP, a final RMSNorm and an untied output head. It runs in
float32 at the highest matmul precision on the same bf16-valued
weights, one layer at a time (a scan casts each layer to float32 as it
goes) and queries in blocks, so that it fits beside the weights.
`control` also runs the fp8 control: every matmul input rounded to
float8 e4m3 (per row of activations, per output column of weights),
the step below the bf16 the configurations state.

Counts: from the model's sizes and the lengths each call served.
Nothing here looks at the grid, the padding or the rows a kernel skips,
so the counts stay the same whatever kernel implements the walk. Model
FLOPs follow the usual inference accounting (launch/cells.py in the
program): 2 x matmul parameters per token, plus the attention score and
value products, 4 x heads x head_dim per key attended."""
from __future__ import annotations

import math
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from harness.counts import _keys_prefill
from harness.model import DTYPES, seed_key
from harness.reference import HI, _mm, _round8

KV_BYTES = 2        # bytes of one K or V element in the arena (bf16)


def model_config(config: dict, engine: dict):
    """The program's ModelConfig for a configuration file: every width
    from the file, the rest from the program's own preset of the
    architecture."""
    from repro.configs import get_arch
    base = get_arch(config["program"]["arch"]).model
    dtype = config["torch_dtype"]
    return base.replace(
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        d_ff=config["intermediate_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        vocab_size=config["vocab_size"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=dtype, param_dtype=dtype,
        max_seq=engine["max_seq"],
        **config["program"].get("overrides", {}))


def dims(config: dict) -> dict:
    """The sizes the reference and the counts need, by short name."""
    return dict(L=config["num_hidden_layers"], d=config["hidden_size"],
                ff=config["intermediate_size"],
                hq=config["num_attention_heads"],
                hkv=config["num_key_value_heads"], hd=config["head_dim"],
                V=config["vocab_size"], theta=float(config["rope_theta"]),
                eps=float(config["rms_norm_eps"]))


def kv_token_bytes(D: dict) -> int:
    """Bytes of K and V that one token keeps in the arena, all layers."""
    return 2 * D["L"] * D["hkv"] * D["hd"] * KV_BYTES


def _layer(key, D: dict, dtype):
    d, ff, L = D["d"], D["ff"], D["L"]
    qd, kvd = D["hq"] * D["hd"], D["hkv"] * D["hd"]
    std, out_std = 0.02, 0.02 / math.sqrt(2 * L)
    ks = jax.random.split(key, 9)

    def w(k, shape, s):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dtype)

    def norm(k):
        return (1.0 + 0.1 * jax.random.normal(k, (d,), jnp.float32)
                ).astype(dtype)

    return {"ln1": norm(ks[0]),
            "attn": {"wq": w(ks[1], (d, qd), std), "wk": w(ks[2], (d, kvd), std),
                     "wv": w(ks[3], (d, kvd), std), "wo": w(ks[4], (qd, d), out_std)},
            "ln2": norm(ks[5]),
            "mlp": {"wg": w(ks[6], (d, ff), std), "wi": w(ks[7], (d, ff), std),
                    "wo": w(ks[8], (ff, d), out_std)}}


def make_weights(config: dict, seed: int, sharding=None):
    """All weights in one jitted call: a scan over layers, so only one
    layer's float32 draw is live at a time."""
    D = dims(config)
    dtype = DTYPES[config["torch_dtype"]]

    def init(key):
        ke, kl, kn, kh = jax.random.split(key, 4)
        _, layers = jax.lax.scan(
            lambda c, k: (c, _layer(k, D, dtype)), 0,
            jax.random.split(kl, D["L"]))
        emb = jax.random.normal(ke, (D["V"], D["d"]), jnp.float32) * 0.02
        head = jax.random.normal(kh, (D["d"], D["V"]), jnp.float32) * 0.02
        ln_f = 1.0 + 0.1 * jax.random.normal(kn, (D["d"],), jnp.float32)
        return {"embed": emb.astype(dtype), "layers": layers,
                "ln_f": ln_f.astype(dtype), "head": head.astype(dtype)}

    fn = jax.jit(init, out_shardings=sharding)
    return jax.block_until_ready(fn(seed_key(seed)))


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


def lower_reference(params, D: dict, *, max_seq: int, max_new: int,
                    sharding, block: int = 512):
    """The reference's program at a cell's sizes, lowered from shapes
    (`params` may be ShapeDtypeStructs), for tools/rehearse.py."""
    block = min(block, max_seq)
    T = padded_length(max_seq, block)

    def arg(n, dtype=jnp.int32):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)

    return _gaps.lower(params, arg(T), arg(max_new), arg(max_new),
                       arg(max_new, jnp.bool_), D=tuple(sorted(D.items())),
                       block=block, control=False)


def matmul_params(D: dict) -> int:
    """Parameters that multiply a token's activations: the layers' four
    attention and three MLP projections, and the output head (the
    embedding is a lookup)."""
    qd, kvd = D["hq"] * D["hd"], D["hkv"] * D["hd"]
    per_layer = D["d"] * qd + 2 * D["d"] * kvd + qd * D["d"] + 3 * D["d"] * D["ff"]
    return D["L"] * per_layer + D["d"] * D["V"]



def model_flops(D: dict, prefill_rows, decode_positions) -> float:
    """prefill_rows: (start, chunk_len) of every row a prefill call
    advanced; decode_positions: the position each decoded row wrote."""
    p2 = 2 * matmul_params(D)
    att = 4 * D["L"] * D["hq"] * D["hd"]
    f = 0.0
    for s, n in prefill_rows:
        f += p2 * n + att * _keys_prefill(s, n)
    for p in decode_positions:
        f += p2 + att * (p + 1)
    return f


def decode_kernel_cost(D: dict, positions, kv_bytes: int,
                       act_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of the paged decode kernel over all layers for rows
    at `positions`: each row reads the K and V of positions 0..p, its
    query, and writes its output."""
    L, hq, hkv, hd = D["L"], D["hq"], D["hkv"], D["hd"]
    flops = nbytes = 0.0
    for p in positions:
        n = p + 1
        flops += 4 * hq * hd * n
        nbytes += 2 * n * hkv * hd * kv_bytes + 2 * hq * hd * act_bytes
    return L * flops, L * nbytes


def prefill_kernel_cost(D: dict, rows, kv_bytes: int,
                        act_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of the paged prefill kernel over all layers for
    chunk rows (start, n): the queries read the K and V of positions
    0..start+n-1 once per row, plus the chunk's queries and outputs."""
    L, hq, hkv, hd = D["L"], D["hq"], D["hkv"], D["hd"]
    flops = nbytes = 0.0
    for s, n in rows:
        flops += 4 * hq * hd * _keys_prefill(s, n)
        nbytes += 2 * (s + n) * hkv * hd * kv_bytes + 2 * n * hq * hd * act_bytes
    return L * flops, L * nbytes
