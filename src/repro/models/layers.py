"""Shared building blocks: norms, rotary, GQA attention, MLPs, embeddings.

Everything is functional: `*_init(key, cfg) -> params`, `*_apply(params,
cfg, x, ...) -> y`, plus a parallel `*_axes(cfg)` returning the logical
sharding axes with the SAME tree structure (tests assert the match).
"""
from __future__ import annotations

import math
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.distribution.sharding import with_logical_constraint


def _normal(key, shape, std, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype) * std


# ----------------------------------------------------------------- RMSNorm

def rmsnorm_init(cfg: ModelConfig, dim: int | None = None):
    return jnp.ones((dim or cfg.d_model,), cfg.params_dtype)


def rmsnorm_axes():
    return ("norm",)


def rmsnorm_apply(scale, x, eps: float):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(dtype)


# ------------------------------------------------------------------ rotary

def rope_frequencies(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta: float):
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq).

    Angles are computed in f32 (position precision), but cos/sin are cast
    to x.dtype BEFORE the rotation: multiplying bf16 activations by f32
    tables makes every q/k COTANGENT f32, which turns all backward TP
    all-reduces and FSDP weight gathers into f32 — a measured 2x wire
    blowup (EXPERIMENTS.md §Perf N4).  bf16 rotation is standard llama
    practice."""
    half = x.shape[-1] // 2
    freqs = rope_frequencies(x.shape[-1], theta)                 # (half,)
    angles = positions[..., None].astype(jnp.float32) * freqs     # (..., seq, half)
    cos = jnp.cos(angles)[..., None, :].astype(x.dtype)           # (..., seq, 1, half)
    sin = jnp.sin(angles)[..., None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1)


# --------------------------------------------------------------- attention

NEG_INF = -1e30


def _repeat_kv(kv, hq: int):
    """(b, s, hkv, d) -> (b, s, hq, d).  Keeps Q-head TP sharding intact:
    the repeat is a device-local broadcast of the (possibly replicated)
    KV heads, so the score einsum shards cleanly over the full head dim."""
    hkv = kv.shape[2]
    if hkv == hq:
        return kv
    return jnp.repeat(kv, hq // hkv, axis=2)


def dense_attention(q, k, v, *, causal: bool, q_offset=0):
    """q: (b, sq, hq, d); k, v: (b, skv, hkv, d).  Reference / small-scale."""
    b, sq, hq, d = q.shape
    k, v = _repeat_kv(k, hq), _repeat_kv(v, hq)
    scale = 1.0 / math.sqrt(d)
    s = jnp.einsum("bqhd,bshd->bhqs", q, k).astype(jnp.float32) * scale
    if causal:
        qpos = jnp.arange(sq)[:, None] + q_offset
        kpos = jnp.arange(k.shape[1])[None, :]
        s = jnp.where(qpos >= kpos, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bhqs,bshd->bqhd", p, v)
    return o


def flash_xla_attention(q, k, v, *, causal: bool, chunk: int, q_offset=0):
    """Online-softmax attention, scanning over KV chunks — linear memory in
    seq_len, compiles on any backend.  (The Pallas kernel is the TPU twin;
    see kernels/flash_attention.)"""
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    chunk = min(chunk, skv)
    pad = (-skv) % chunk
    if pad:  # pad KV to a chunk multiple; padded slots are masked out below
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n = (skv + pad) // chunk
    scale = 1.0 / math.sqrt(d)

    kc = jnp.moveaxis(k.reshape(b, n, chunk, hkv, d), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, n, chunk, hkv, d), 1, 0)
    kv_pos = jnp.arange(n * chunk).reshape(n, chunk)
    q_pos = jnp.arange(sq) + q_offset

    def body(carry, xs):
        m, l, acc = carry
        k_i, v_i, pos_i = xs
        k_i, v_i = _repeat_kv(k_i, hq), _repeat_kv(v_i, hq)
        s = jnp.einsum("bqhd,bshd->bhqs", q, k_i).astype(jnp.float32) * scale
        valid = pos_i < skv
        if causal:
            valid = valid & (q_pos[:, None] >= pos_i[None, :])
        if causal or pad:
            s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhqs,bshd->bhqd", p.astype(v_i.dtype), v_i)
        acc_new = acc * corr[..., None] + pv.astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hq, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hq, sq), jnp.float32)
    acc0 = jnp.zeros((b, hq, sq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (kc, vc, kv_pos))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    o = jnp.moveaxis(o, 1, 2)                                     # (b,sq,hq,d)
    return o.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, position):
    """Single-token decode: q (b, hq, d); caches (b, S, hkv, d) sharded
    along S over "model" (near-memory resident KV slices); position: (b,)
    = index of the newly written token.  The softmax reductions over the
    sharded S dim become small per-(b,h) all-reduces under SPMD — the
    'broadcast query, reduce partial results' dataflow of the paper."""
    b, hq, d = q.shape
    S, hkv = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, d)
    scale = 1.0 / math.sqrt(d)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k_cache).astype(jnp.float32) * scale
    mask = jnp.arange(S)[None, :] <= position[:, None]            # (b, S)
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    o = jnp.einsum("bhgs,bshd->bhgd", p, v_cache)
    return o.reshape(b, hq * d)


def run_decode_attention(cfg: ModelConfig, q, k_cache, v_cache, position):
    """Config-dispatched decode attention: the XLA path above, or the
    split-KV Pallas kernel (flash-decoding) when attention_impl is
    flash_pallas — the paper's resident-KV / broadcast-query dataflow."""
    if cfg.attention_impl == "flash_pallas":
        from repro.kernels.decode_attention.ops import decode_attention as da
        b, hq, d = q.shape
        return da(q, k_cache, v_cache, position).reshape(b, hq * d)
    return decode_attention(q, k_cache, v_cache, position)


def localize_block_table(cfg: ModelConfig, block_table, num_local_pages):
    """GLOBAL pool page ids -> this shard's bank slots for page WRITES:
    entries the shard owns become local slots, everything else (other
    shards' pages, the null sentinel) its local null sink
    (`num_local_pages`).  Identity when `cfg.mem_axis` is unset (single
    arena — the table already is physical).  Rotation-agnostic: writes
    address pages by PHYSICAL id; only the attention walk needs the
    logical stride."""
    if cfg.mem_axis is None:
        return block_table
    pps = num_local_pages
    idx = jax.lax.axis_index(cfg.mem_axis)
    return jnp.where(block_table // pps == idx, block_table % pps,
                     pps).astype(jnp.int32)


def _shard_local_walk(mem_axis: str, block_table, page_size: int,
                      local_null: int):
    """Compact a shard's walk of a GLOBAL block table to its resident
    stride (DESIGN.md §2 page→shard mapping): logical page j of a
    sequence lives on shard (rot + j) % n, where rot is the sequence's
    per-prompt ROTATION — recovered here as the shard owning its logical
    page 0 (`block_table[:, 0] // pps`), so the allocator can rotate
    placement per prompt (bank balance under many-short-prompt loads)
    without any extra step input.  The columns shard `idx` must walk for
    row i are exactly j ≡ idx - rot_i (mod n).

    block_table: (b, max_pages) GLOBAL page ids.  Returns the
    (b, ceil(max_pages/n)) compacted LOCAL table + its absolute page
    positions (POS_PAD sentinel for null/foreign/absent slots, so the
    kernels' position mask kills them unconditionally): each chip's
    attention walk is n times shorter — KV bandwidth scales with the
    mesh."""
    from repro.kernels.paged_attention.kernel import POS_PAD

    n = jax.lax.axis_size(mem_axis)
    idx = jax.lax.axis_index(mem_axis)
    pps = local_null                       # bank size == local null slot
    b, mp = block_table.shape
    mp_loc = -(-mp // n)
    # per-row rotation from the table itself; inactive rows (all-null)
    # clamp to n — their columns are masked below regardless
    rot = jnp.minimum(block_table[:, 0] // pps, n)
    col0 = jnp.mod(idx - rot, n).astype(jnp.int32)           # (b,)
    cols = col0[:, None] + n * jnp.arange(mp_loc, dtype=jnp.int32)[None, :]
    safe = jnp.minimum(cols, mp - 1)
    gbt = jnp.take_along_axis(block_table, safe, axis=1)     # (b, mp_loc)
    resident = (cols < mp) & (gbt // pps == idx)
    lbt = jnp.where(resident, gbt % pps, pps).astype(jnp.int32)
    page_pos = jnp.where(resident, cols * page_size, POS_PAD)
    return lbt, page_pos.astype(jnp.int32)


def fused_paged_kernels(cfg: ModelConfig) -> bool:
    """Whether the paged walk runs the fused Pallas kernels.  Off the
    CPU backend it always does, whatever `attention_impl` says.  On the
    CPU the XLA gather oracle serves, unless the config asks for
    `flash_pallas` (the kernels then run in the Pallas interpreter)."""
    return (cfg.attention_impl == "flash_pallas"
            or jax.default_backend() != "cpu")


def run_paged_decode_attention(cfg: ModelConfig, q, k_pages, v_pages,
                               block_table, positions,
                               k_scale=None, v_scale=None):
    """Config-dispatched paged decode attention over the UniMem arena.

    q: (b, hq, d); k_pages/v_pages: (P, page, hkv, d) ONE layer's
    physical page arena; block_table: (b, max_pages) page-table rows;
    positions: (b,) inclusive newest index.  Routes to the fused
    single-pass Pallas block-table kernel (resident pages, travelling
    query, VMEM online-softmax carry — `cfg.attn_pages_per_block` pages
    per sequential grid cell), or on the CPU to the XLA gather oracle
    (`fused_paged_kernels`).  Returns (b, hq*d).

    With `cfg.mem_axis` set (inside the shard_map'd sharded serving
    step, where `block_table` carries GLOBAL pool ids) each chip
    recovers the sequence's placement rotation from the table, attends
    over its RESIDENT pages only in partials mode, and the
    (b, hq(, d))-sized summaries are log-sum-exp-merged across the mesh
    — the near-memory dataflow: pages stay put, summaries travel.

    `k_scale`/`v_scale` ((P, page, hkv) f32, quantized arenas) ride the
    same block-table walk — the sharded compacted table indexes the
    LOCAL scale banks exactly as it indexes the local pages."""
    b, hq, d = q.shape
    kw = {}
    if k_scale is not None:
        kw = dict(k_scale=k_scale, v_scale=v_scale)
    if cfg.mem_axis is not None:
        lbt, page_pos = _shard_local_walk(
            cfg.mem_axis, block_table, k_pages.shape[1],
            local_null=k_pages.shape[0] - 1)
        block_table = lbt
        kw.update(page_positions=page_pos, partials=True)
    if fused_paged_kernels(cfg):
        from repro.kernels.paged_attention.ops import paged_decode_attention
        o = paged_decode_attention(q, k_pages, v_pages, block_table, positions,
                                   pages_per_block=cfg.attn_pages_per_block,
                                   **kw)
    else:
        from repro.kernels.paged_attention.ref import paged_decode_attention_ref
        o = paged_decode_attention_ref(q, k_pages, v_pages, block_table,
                                       positions, **kw)
    if cfg.mem_axis is not None:
        from repro.distribution.collectives import combine_shard_partials
        o = combine_shard_partials(*o, cfg.mem_axis, q.dtype)
    return o.reshape(b, hq * d)


def run_paged_prefill_attention(cfg: ModelConfig, q, k_pages, v_pages,
                                block_table, start, chunk_len,
                                k_scale=None, v_scale=None):
    """Config-dispatched causal chunk-prefill attention over the arena.

    q: (b, c, hq, d) chunk queries at absolute positions
    start[i]..start[i]+c-1; k_pages/v_pages: (P, page, hkv, d) ONE
    layer's arena (the chunk's own K/V already written); chunk_len: (b,)
    ragged valid rows (rows past it come back as zeros).  The fused
    Pallas kernel walks the block table in place — the
    (b, max_pages*page, hkv, hd) gathered KV copy of the XLA gather
    oracle (the CPU path, `fused_paged_kernels`) never exists.
    Returns (b, c, hq*d).  Per-chunk cost is c*S, not prompt^2.

    With `cfg.mem_axis` set (sharded serving step, GLOBAL block table),
    each chip walks only its resident pages (rotation-aware stride) and
    the (b, c, hq(, d)) chunk summaries merge across the mesh — see
    `run_paged_decode_attention` (scale banks included)."""
    b, c, hq, d = q.shape
    kw = {}
    if k_scale is not None:
        kw = dict(k_scale=k_scale, v_scale=v_scale)
    if cfg.mem_axis is not None:
        lbt, page_pos = _shard_local_walk(
            cfg.mem_axis, block_table, k_pages.shape[1],
            local_null=k_pages.shape[0] - 1)
        block_table = lbt
        kw.update(page_positions=page_pos, partials=True)
    if fused_paged_kernels(cfg):
        from repro.kernels.paged_prefill.ops import paged_prefill_attention
        o = paged_prefill_attention(q, k_pages, v_pages, block_table,
                                    start, chunk_len,
                                    pages_per_block=cfg.attn_pages_per_block,
                                    **kw)
    else:
        from repro.kernels.paged_prefill.ref import paged_prefill_attention_ref
        o = paged_prefill_attention_ref(q, k_pages, v_pages, block_table,
                                        start, chunk_len, **kw)
    if cfg.mem_axis is not None:
        from repro.distribution.collectives import combine_shard_partials
        o = combine_shard_partials(*o, cfg.mem_axis, q.dtype)
    return o.reshape(b, c, hq * d)


def run_attention(cfg: ModelConfig, q, k, v, *, q_offset=0):
    if cfg.attention_impl == "dense":
        return dense_attention(q, k, v, causal=cfg.causal, q_offset=q_offset)
    if cfg.attention_impl == "flash_xla":
        return flash_xla_attention(q, k, v, causal=cfg.causal,
                                   chunk=cfg.attn_chunk, q_offset=q_offset)
    if cfg.attention_impl == "flash_pallas":
        from repro.kernels.flash_attention import ops as fa_ops
        return fa_ops.flash_attention(q, k, v, causal=cfg.causal,
                                      block_kv=cfg.attn_chunk,
                                      q_offset=q_offset)
    raise ValueError(f"unknown attention_impl {cfg.attention_impl!r}")


# ---------------------------------------------------------- attention block

def attention_init(key, cfg: ModelConfig):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    std = 0.02
    out_std = std / math.sqrt(2 * cfg.num_layers)
    return {
        "wq": _normal(k1, (d, qd), std, cfg.params_dtype),
        "wk": _normal(k2, (d, kvd), std, cfg.params_dtype),
        "wv": _normal(k3, (d, kvd), std, cfg.params_dtype),
        "wo": _normal(k4, (qd, d), out_std, cfg.params_dtype),
    }


def attention_axes():
    return {
        "wq": ("embed", "heads"),
        "wk": ("embed", "kv_heads"),
        "wv": ("embed", "kv_heads"),
        "wo": ("heads", "embed"),
    }


def attention_qkv(p, cfg: ModelConfig, x, positions):
    """x: (b, s, d) -> q (b,s,hq,hd), k/v (b,s,hkv,hd) with rope applied."""
    b, s, _ = x.shape
    q = (x @ p["wq"]).reshape(b, s, cfg.num_heads, cfg.head_dim)
    k = (x @ p["wk"]).reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    v = (x @ p["wv"]).reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    if cfg.rope_theta > 0:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = with_logical_constraint(q, "act_batch", "act_seq", "act_heads", None)
    k = with_logical_constraint(k, "act_batch", "act_seq", "act_kv_heads", None)
    v = with_logical_constraint(v, "act_batch", "act_seq", "act_kv_heads", None)
    return q, k, v


def attention_apply(p, cfg: ModelConfig, x, positions):
    """Full self-attention over x: (b, s, d)."""
    b, s, _ = x.shape
    q, k, v = attention_qkv(p, cfg, x, positions)
    o = run_attention(cfg, q, k, v)
    o = o.reshape(b, s, cfg.q_dim)
    y = o @ p["wo"]
    return with_logical_constraint(y, "act_batch", "act_seq", "act_embed")


# --------------------------------------------------------------------- MLP

def mlp_init(key, cfg: ModelConfig, d_in: int | None = None, d_ff: int | None = None):
    d = d_in or cfg.d_model
    f = d_ff or cfg.d_ff
    std = 0.02
    out_std = std / math.sqrt(2 * cfg.num_layers)
    ks = jax.random.split(key, 3)
    p = {"wo": _normal(ks[2], (f, d), out_std, cfg.params_dtype)}
    if cfg.activation == "silu_glu":
        p["wg"] = _normal(ks[0], (d, f), std, cfg.params_dtype)
        p["wi"] = _normal(ks[1], (d, f), std, cfg.params_dtype)
    else:
        p["wi"] = _normal(ks[1], (d, f), std, cfg.params_dtype)
    return p


def mlp_axes(cfg: ModelConfig):
    ax = {"wo": ("mlp", "embed")}
    if cfg.activation == "silu_glu":
        ax["wg"] = ("embed", "mlp")
    ax["wi"] = ("embed", "mlp")
    return ax


def mlp_apply(p, cfg: ModelConfig, x):
    if cfg.activation == "silu_glu":
        h = jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])
    elif cfg.activation == "relu2":
        h = jnp.square(jax.nn.relu(x @ p["wi"]))
    elif cfg.activation == "gelu":
        h = jax.nn.gelu(x @ p["wi"])
    else:
        raise ValueError(cfg.activation)
    h = with_logical_constraint(h, "act_batch", "act_seq", "act_mlp")
    y = h @ p["wo"]
    return with_logical_constraint(y, "act_batch", "act_seq", "act_embed")


# -------------------------------------------------------------- embeddings

def embedding_init(key, cfg: ModelConfig):
    return _normal(key, (cfg.vocab_size, cfg.d_model), 0.02, cfg.params_dtype)


def embedding_axes():
    # Vocab-parallel table; the d dim stays replicated: activations are
    # batch-sharded, so a data-sharded table d-dim would force an
    # all-to-all (XLA falls back to full-table rematerialization —
    # measured as an f32[vocab, d_model] all-reduce per microbatch on the
    # gradient path; EXPERIMENTS.md §Perf N3).
    return ("vocab", None)


def _vocab_parallel_lookup(emb, cfg: ModelConfig, tokens, mesh):
    """Megatron-style vocab-parallel embedding: each model shard gathers
    ids in ITS vocab range from its RESIDENT table rows (masked-local),
    then one activation-sized psum combines.  The backward is a LOCAL
    scatter-add — the table-sized gradient never crosses the fabric."""
    from jax.sharding import PartitionSpec as P
    from repro.distribution.sharding import logical_to_spec
    from functools import partial

    emb_spec = logical_to_spec(("vocab", None), tuple(emb.shape), mesh)
    tok_spec = logical_to_spec(("act_batch", None), tuple(tokens.shape), mesh)
    out_spec = P(*(tuple(tok_spec) + (None,)))
    dtype = cfg.compute_dtype

    def local(emb_l, tok_l):
        v_loc = emb_l.shape[0]
        start = jax.lax.axis_index("model") * v_loc
        rel = tok_l - start
        ok = (rel >= 0) & (rel < v_loc)
        x = jnp.take(emb_l, jnp.clip(rel, 0, v_loc - 1), axis=0)
        x = jnp.where(ok[..., None], x.astype(dtype), jnp.zeros((), dtype))
        return jax.lax.psum(x, "model")

    fn = jax.shard_map(local, mesh=mesh, in_specs=(emb_spec, tok_spec),
                       out_specs=out_spec, check_vma=False)
    return fn(emb, tokens)


def embed_tokens(emb, cfg: ModelConfig, tokens):
    from repro.distribution.sharding import current_mesh
    mesh = current_mesh()
    if (mesh is not None and "model" in mesh.axis_names
            and mesh.shape["model"] > 1
            and cfg.vocab_size % mesh.shape["model"] == 0):
        x = _vocab_parallel_lookup(emb, cfg, tokens, mesh)
    else:
        x = jnp.take(emb, tokens, axis=0).astype(cfg.compute_dtype)
    return with_logical_constraint(x, "act_batch", "act_seq", "act_embed")


def logits_from_hidden(emb_or_head, cfg: ModelConfig, x):
    """x: (b, s, d) @ head (d, vocab) or tied embedding (vocab, d)."""
    w = emb_or_head
    if w.shape[0] == cfg.vocab_size:          # tied: (vocab, d)
        logits = jnp.einsum("bsd,vd->bsv", x, w.astype(x.dtype))
    else:
        logits = x @ w.astype(x.dtype)
    return with_logical_constraint(logits, "act_batch", "act_seq", "act_vocab")


# -------------------------------------------------------------------- loss

def cross_entropy(logits, labels):
    """Mean CE over positions with label >= 0.  logits: (..., V)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1
    )[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    n = jnp.maximum(mask.sum(), 1.0)
    return ((lse - ll) * mask).sum() / n


def chunked_ce_loss(hidden, head, cfg: ModelConfig, labels, chunk: int):
    """Scan over seq chunks, computing logits per chunk — O(chunk*vocab)
    live memory instead of O(seq*vocab).  Returns (sum_loss, count)."""
    b, s, d = hidden.shape
    assert s % chunk == 0
    n = s // chunk
    hc = jnp.moveaxis(hidden.reshape(b, n, chunk, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(b, n, chunk), 1, 0)

    def body(carry, xs):
        tot, cnt = carry
        h, lab = xs
        logits = logits_from_hidden(head, cfg, h).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, jnp.maximum(lab, 0)[..., None], axis=-1)[..., 0]
        mask = (lab >= 0).astype(jnp.float32)
        return (tot + ((lse - ll) * mask).sum(), cnt + mask.sum()), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.float32(0.0)), (hc, lc))
    return tot / jnp.maximum(cnt, 1.0)


def lm_loss(hidden, head, cfg: ModelConfig, labels):
    if cfg.logits_chunk and hidden.shape[1] % cfg.logits_chunk == 0:
        return chunked_ce_loss(hidden, head, cfg, labels, cfg.logits_chunk)
    logits = logits_from_hidden(head, cfg, hidden)
    return cross_entropy(logits, labels)
