"""Mixture-of-Experts transformer (qwen3-moe / moonshot-moonlight families).

Top-k token-choice routing with capacity-based dispatch.  Dispatch
paths (cfg.moe_dispatch):

  * "dense"   — one-hot einsum dispatch; O(T*E*C) memory.  Oracle for
                tests and small smoke configs.
  * "scatter" — sort-by-expert + positional scatter into per-expert
                capacity buffers; O(T*k) bookkeeping, shards over the
                mesh ("expert" -> model axis, capacity rows -> data axis).
                This is the paper's "vectors as the basic computational
                unit" realized as expert-parallel vector dispatch.
  * "grouped" — scatter dispatch with the per-expert matmul stack run
                through the grouped-matmul Pallas kernel
                (kernels/grouped_matmul) instead of its einsum twin —
                the serving path's expert dispatch on the MXU.
  * "ep"      — expert-parallel shard_map (resident experts per model
                shard); falls back to scatter off-mesh.

All are differentiable; tests assert they agree.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models import layers as L
from repro.models import transformer as T
from repro.distribution.sharding import with_logical_constraint


# ------------------------------------------------------------ expert stack

def experts_init(key, cfg: ModelConfig):
    e, d, f = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
    std = 0.02
    out_std = std / math.sqrt(2 * cfg.num_layers)
    ks = jax.random.split(key, 3)
    return {
        "wg": L._normal(ks[0], (e, d, f), std, cfg.params_dtype),
        "wi": L._normal(ks[1], (e, d, f), std, cfg.params_dtype),
        "wo": L._normal(ks[2], (e, f, d), out_std, cfg.params_dtype),
    }


def experts_axes():
    return {
        "wg": ("expert", "expert_in", "mlp"),
        "wi": ("expert", "expert_in", "mlp"),
        "wo": ("expert", "mlp", "expert_in"),
    }


def experts_apply(p, buf):
    """buf: (E, C, d) -> (E, C, d) through each expert's GLU MLP."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wg"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["wi"])
    h = with_logical_constraint(h, "act_expert", "act_cap", "act_mlp")
    out = jnp.einsum("ecf,efd->ecd", h, p["wo"])
    return with_logical_constraint(out, "act_expert", "act_cap", None)


def _pad_tile(a, axes, tile=128):
    """Zero-pad `axes` of a up to a tile multiple where they exceed one
    tile (the kernel requires dim % min(tile, dim) == 0; zero rows/cols
    compute zeros and cannot perturb real outputs)."""
    pads = [(0, 0)] * a.ndim
    for ax in axes:
        n = a.shape[ax]
        if n > tile:
            pads[ax] = (0, (-n) % tile)
    return jnp.pad(a, pads) if any(p != (0, 0) for p in pads) else a


def experts_apply_grouped(p, buf):
    """`experts_apply` through the grouped-matmul Pallas kernel — the
    per-expert weight-stationary MXU stack (interpret mode off-TPU).
    Capacity, d_model and d_ff are zero-padded to the kernel's 128
    tiling where needed."""
    from repro.kernels.grouped_matmul.ops import grouped_matmul

    e, c, d = buf.shape
    x = _pad_tile(buf, (1, 2))
    wg = _pad_tile(p["wg"], (1, 2))
    wi = _pad_tile(p["wi"], (1, 2))
    wo = _pad_tile(p["wo"], (1, 2))
    h = jax.nn.silu(grouped_matmul(x, wg)) * grouped_matmul(x, wi)
    out = grouped_matmul(h.astype(buf.dtype), wo).astype(buf.dtype)
    return out[:, :c, :d]


# ----------------------------------------------------------------- routing

def router_init(key, cfg: ModelConfig):
    return L._normal(key, (cfg.d_model, cfg.num_experts), 0.02, cfg.params_dtype)


def _route(router_w, cfg: ModelConfig, xf):
    """xf: (T, d) -> (weights (T, k), experts (T, k), aux_loss)."""
    logits = (xf @ router_w).astype(jnp.float32)                  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, cfg.experts_per_token)    # (T, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    # load-balance aux (Switch-style) + router z-loss
    T_ = xf.shape[0]
    me = probs.mean(axis=0)                                       # (E,)
    ce = jnp.zeros((cfg.num_experts,), jnp.float32)
    ce = ce.at[top_e.reshape(-1)].add(1.0) / (T_ * cfg.experts_per_token)
    aux = cfg.num_experts * jnp.sum(me * ce) * cfg.router_aux_coef
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2) * cfg.router_z_coef
    return top_w, top_e, aux + z


def _capacity(cfg: ModelConfig, num_tokens: int) -> int:
    c = int(math.ceil(num_tokens * cfg.experts_per_token / cfg.num_experts
                      * cfg.capacity_factor))
    return max(8, -(-c // 8) * 8)  # round up to 8


# -------------------------------------------------------- dispatch: dense

def _moe_dense(p, cfg: ModelConfig, xf):
    """One-hot dispatch oracle.  xf: (T, d)."""
    T_, d = xf.shape
    C = _capacity(cfg, T_)
    w, e, aux = _route(p["router"], cfg, xf)
    k = cfg.experts_per_token
    # position of each (token, choice) within its expert's capacity
    onehot = jax.nn.one_hot(e, cfg.num_experts, dtype=jnp.int32)   # (T, k, E)
    flat = onehot.reshape(T_ * k, cfg.num_experts)
    pos = jnp.cumsum(flat, axis=0) - flat                          # (T*k, E)
    pos = (pos * flat).sum(-1).reshape(T_, k)                      # (T, k)
    keep = pos < C
    disp = (jax.nn.one_hot(e, cfg.num_experts, dtype=xf.dtype)[..., :, None]
            * jax.nn.one_hot(pos, C, dtype=xf.dtype)[..., None, :]
            * keep[..., None, None].astype(xf.dtype))              # (T,k,E,C)
    buf = jnp.einsum("td,tkec->ecd", xf, disp)                     # (E, C, d)
    out_buf = experts_apply(p["experts"], buf)
    y = jnp.einsum("ecd,tkec,tk->td", out_buf, disp, w.astype(xf.dtype))
    return y, aux


# ------------------------------------------------------ dispatch: scatter

def _moe_scatter(p, cfg: ModelConfig, xf, experts_fn=experts_apply,
                 dropless=False):
    """Sort-based dispatch.  xf: (T, d).  `experts_fn` is the per-expert
    MLP stack: the einsum twin by default, the grouped-matmul Pallas
    kernel under moe_dispatch="grouped".  `dropless=True` (the SERVING
    mode) sizes capacity at the worst case T*k so no assignment is ever
    dropped: every token's output is then a pure per-token function,
    independent of what else shares the batch — which is what makes
    paged serving exact (padded rows can't evict real tokens; identical
    prompts compute bitwise-identical K/V in any batch, so prefix
    sharing and co-prefill page writes are safe)."""
    T_, d = xf.shape
    k = cfg.experts_per_token
    E = cfg.num_experts
    C = T_ * k if dropless else _capacity(cfg, T_)
    w, e, aux = _route(p["router"], cfg, xf)

    e_flat = e.reshape(-1)                                         # (T*k,)
    order = jnp.argsort(e_flat)                                    # stable
    e_sorted = e_flat[order]
    tok_sorted = order // k                                        # token ids
    # position within expert = rank - first-rank-of-that-expert
    counts = jnp.bincount(e_sorted, length=E)
    starts = jnp.cumsum(counts) - counts                           # (E,)
    ranks = jnp.arange(T_ * k)
    pos_sorted = ranks - starts[e_sorted]                          # (T*k,)
    keep = pos_sorted < C
    pos_c = jnp.where(keep, pos_sorted, C - 1)

    rows = xf[tok_sorted]                                          # (T*k, d)
    rows = rows * keep[:, None].astype(xf.dtype)
    buf = jnp.zeros((E, C, d), xf.dtype)
    buf = buf.at[e_sorted, pos_c].add(rows, mode="drop")
    buf = with_logical_constraint(buf, "act_expert", "act_cap", None)

    out_buf = experts_fn(p["experts"], buf)

    y_rows = out_buf[e_sorted, pos_c]                              # (T*k, d)
    y_rows = y_rows * keep[:, None].astype(xf.dtype)
    # un-sort back to (T, k, d) then weighted-combine
    y_flat = jnp.zeros((T_ * k, d), xf.dtype).at[order].set(y_rows)
    y = jnp.einsum("tkd,tk->td", y_flat.reshape(T_, k, d), w.astype(xf.dtype))
    return y, aux


# ------------------------------------------------- dispatch: EP shard_map
#
# The global sort-scatter above leaves XLA's SPMD partitioner no good
# sharding for the (T*k, d) gather/scatter — it replicates them
# (measured: 212 GB/device temp on qwen3-moe x train_4k, §Perf M1).
# The expert-parallel path does the paper's vector dispatch the way the
# chip does it: each data shard routes ITS OWN token vectors, builds
# capacity buffers only for the experts RESIDENT on its model shard
# (weight-stationary), and the only fabric traffic is the psum of the
# combined outputs over the model axis — "results are sent back to the
# central memory pool".

def _moe_ep_local(cfg: ModelConfig, model_axis: str, other_axes: tuple,
                  router_w, wg, wi, wo, xl):
    """Per-shard body (inside shard_map).  xl: (T_loc, d) local tokens;
    wg/wi/wo: (E_loc, ...) resident expert shards."""
    E = cfg.num_experts
    E_loc = wg.shape[0]
    m_idx = jax.lax.axis_index(model_axis)
    T_loc, d = xl.shape
    k = cfg.experts_per_token
    C = _capacity(cfg, T_loc)

    w, e, aux = _route(router_w, cfg, xl)              # full-E routing
    e_flat = e.reshape(-1)
    order = jnp.argsort(e_flat)
    e_sorted = e_flat[order]
    tok_sorted = order // k
    counts = jnp.bincount(e_sorted, length=E)
    starts = jnp.cumsum(counts) - counts
    pos_sorted = jnp.arange(T_loc * k) - starts[e_sorted]
    keep = pos_sorted < C
    pos_c = jnp.where(keep, pos_sorted, C - 1)

    # resident-expert selection: foreign experts are redirected to the
    # explicitly out-of-bounds index E_loc and DROPPED by the scatter
    # (negative indices would WRAP, not drop — they must never reach it)
    e_rel = e_sorted - m_idx * E_loc
    mine_e = (e_rel >= 0) & (e_rel < E_loc)
    e_idx = jnp.where(mine_e, e_rel, E_loc)
    rows = xl[tok_sorted] * keep[:, None].astype(xl.dtype)
    buf = jnp.zeros((E_loc, C, d), xl.dtype)
    buf = buf.at[e_idx, pos_c].add(rows, mode="drop")

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
    h = h * jnp.einsum("ecd,edf->ecf", buf, wi)
    out_buf = jnp.einsum("ecf,efd->ecd", h, wo)

    mine = keep & mine_e
    e_rel_c = jnp.clip(e_rel, 0, E_loc - 1)
    y_rows = out_buf[e_rel_c, pos_c] * mine[:, None].astype(xl.dtype)
    y_flat = jnp.zeros((T_loc * k, d), xl.dtype).at[order].set(y_rows)
    y = jnp.einsum("tkd,tk->td", y_flat.reshape(T_loc, k, d),
                   w.astype(xl.dtype))
    y = jax.lax.psum(y, model_axis)                    # combine: results out
    if other_axes:
        aux = jax.lax.pmean(aux, other_axes)           # consistent scalar
    return y, aux


def _moe_ep(p, cfg: ModelConfig, xf):
    """Expert-parallel dispatch via shard_map; falls back to the global
    scatter when no mesh (or an indivisible expert count) is active."""
    from jax.sharding import PartitionSpec as P
    from repro.distribution.sharding import current_mesh, logical_to_spec
    from functools import partial

    mesh = current_mesh()
    if (mesh is None or "model" not in mesh.axis_names
            or cfg.num_experts % mesh.shape["model"] != 0):
        return _moe_scatter(p, cfg, xf)
    other = tuple(a for a in mesh.axis_names if a != "model")
    x_spec = logical_to_spec(("act_batch", None), tuple(xf.shape), mesh)
    w_spec = P("model", None, None)
    fn = jax.shard_map(
        partial(_moe_ep_local, cfg, "model", other),
        mesh=mesh,
        in_specs=(P(), w_spec, w_spec, w_spec, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    return fn(p["router"], p["experts"]["wg"], p["experts"]["wi"],
              p["experts"]["wo"], xf)


def moe_block_init(key, cfg: ModelConfig):
    kr, ke, ks = jax.random.split(key, 3)
    p = {"router": router_init(kr, cfg), "experts": experts_init(ke, cfg)}
    if cfg.num_shared_experts:
        p["shared"] = L.mlp_init(ks, cfg, d_ff=cfg.num_shared_experts * cfg.moe_d_ff)
    return p


def moe_block_axes(cfg: ModelConfig):
    ax = {"router": ("embed", "norm"), "experts": experts_axes()}
    if cfg.num_shared_experts:
        ax["shared"] = L.mlp_axes(cfg)
    return ax


def moe_apply(p, cfg: ModelConfig, x, dropless=False):
    """x: (b, s, d) -> (y, aux_loss).  `dropless=True` is the SERVING
    mode: worst-case expert capacity, no token ever dropped, outputs a
    pure per-token function independent of batch composition (see
    `_moe_scatter`).  Training keeps the capacity-limited dispatch."""
    if cfg.moe_dispatch not in ("dense", "scatter", "grouped", "ep"):
        raise ValueError(cfg.moe_dispatch)
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    xf = with_logical_constraint(xf, "act_batch", None)
    if cfg.moe_dispatch == "grouped":
        y, aux = _moe_scatter(p, cfg, xf, experts_fn=experts_apply_grouped,
                              dropless=dropless)
    elif dropless:
        # dense/ep are training dataplanes; dropless serving takes the
        # equivalent global scatter
        y, aux = _moe_scatter(p, cfg, xf, dropless=True)
    elif cfg.moe_dispatch == "dense":
        y, aux = _moe_dense(p, cfg, xf)
    elif cfg.moe_dispatch == "scatter":
        y, aux = _moe_scatter(p, cfg, xf)
    else:
        y, aux = _moe_ep(p, cfg, xf)
    y = y.reshape(b, s, d)
    if cfg.num_shared_experts:
        y = y + L.mlp_apply(p["shared"], cfg, x)
    return with_logical_constraint(y, "act_batch", "act_seq", "act_embed"), aux


# ------------------------------------------------------------------ model

def layer_init(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": L.rmsnorm_init(cfg),
        "attn": L.attention_init(k1, cfg),
        "ln2": L.rmsnorm_init(cfg),
        "moe": moe_block_init(k2, cfg),
    }


def layer_axes(cfg: ModelConfig):
    return {
        "ln1": L.rmsnorm_axes(),
        "attn": L.attention_axes(),
        "ln2": L.rmsnorm_axes(),
        "moe": moe_block_axes(cfg),
    }


def layer_apply(p, cfg: ModelConfig, x, positions):
    h = L.rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
    x = x + L.attention_apply(p["attn"], cfg, h, positions)
    h = L.rmsnorm_apply(p["ln2"], x, cfg.norm_eps)
    y, aux = moe_apply(p["moe"], cfg, h)
    return x + y, aux


def init(key, cfg: ModelConfig):
    ke, kl, kh = jax.random.split(key, 3)
    layer_keys = jax.random.split(kl, cfg.num_layers)
    stacked = jax.vmap(lambda k: layer_init(k, cfg))(layer_keys)
    params = {
        "embed": L.embedding_init(ke, cfg),
        "layers": stacked,
        "ln_f": L.rmsnorm_init(cfg),
    }
    if not cfg.tie_embeddings:
        params["head"] = L._normal(kh, (cfg.d_model, cfg.vocab_size), 0.02,
                                   cfg.params_dtype)
    return params


def param_axes(cfg: ModelConfig):
    stacked = jax.tree.map(lambda ax: ("stage",) + ax, layer_axes(cfg),
                           is_leaf=lambda x: isinstance(x, tuple))
    axes = {
        "embed": L.embedding_axes(),
        "layers": stacked,
        "ln_f": L.rmsnorm_axes(),
    }
    if not cfg.tie_embeddings:
        axes["head"] = ("embed", "vocab")
    return axes


def forward_hidden(params, cfg: ModelConfig, x, positions):
    def body(carry, p):
        h, aux = carry
        h, a = layer_apply(p, cfg, h, positions)
        return (h, aux + a), None

    body = T._maybe_remat(body, cfg)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), params["layers"])
    return L.rmsnorm_apply(params["ln_f"], x, cfg.norm_eps), aux


def forward(params, cfg: ModelConfig, batch):
    tokens = batch["tokens"]
    positions = jnp.arange(tokens.shape[1])[None, :]
    x = L.embed_tokens(params["embed"], cfg, tokens)
    h, _ = forward_hidden(params, cfg, x, positions)
    return L.logits_from_hidden(T.head_weights(params, cfg), cfg, h)


def loss_fn(params, cfg: ModelConfig, batch):
    tokens, labels = batch["tokens"], batch["labels"]
    positions = jnp.arange(tokens.shape[1])[None, :]
    x = L.embed_tokens(params["embed"], cfg, tokens)
    h, aux = forward_hidden(params, cfg, x, positions)
    return L.lm_loss(h, T.head_weights(params, cfg), cfg, labels) + aux


# ---------------------------------------------------------------- serving

init_cache = T.init_cache
cache_axes = T.cache_axes


def prefill(params, cfg: ModelConfig, batch, cache):
    tokens = batch["tokens"]
    b, s = tokens.shape
    positions = jnp.arange(s)[None, :]
    x = L.embed_tokens(params["embed"], cfg, tokens)

    def body(h, xs):
        p, k_l, v_l = xs
        hn = L.rmsnorm_apply(p["ln1"], h, cfg.norm_eps)
        q, k, v = L.attention_qkv(p["attn"], cfg, hn, positions)
        o = L.run_attention(cfg, q, k, v).reshape(b, s, cfg.q_dim)
        h = h + o @ p["attn"]["wo"]
        hn = L.rmsnorm_apply(p["ln2"], h, cfg.norm_eps)
        y, _ = moe_apply(p["moe"], cfg, hn, dropless=True)
        h = h + y
        k_l = jax.lax.dynamic_update_slice(k_l, k.astype(k_l.dtype), (0, 0, 0, 0))
        v_l = jax.lax.dynamic_update_slice(v_l, v.astype(v_l.dtype), (0, 0, 0, 0))
        return h, (k_l, v_l)

    x, (k_new, v_new) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
    cache = {"k": k_new, "v": v_new, "pos": jnp.full((b,), s, jnp.int32)}
    h = L.rmsnorm_apply(params["ln_f"], x[:, -1:], cfg.norm_eps)
    logits = L.logits_from_hidden(T.head_weights(params, cfg), cfg, h)
    return cache, logits[:, 0]


def decode_step(params, cfg: ModelConfig, cache, tokens):
    b = tokens.shape[0]
    pos = cache["pos"]
    x = L.embed_tokens(params["embed"], cfg, tokens[:, None])

    def body(h, xs):
        p, k_l, v_l = xs
        hn = L.rmsnorm_apply(p["ln1"], h, cfg.norm_eps)
        q, k, v = L.attention_qkv(p["attn"], cfg, hn, pos[:, None])
        k_l = T._scatter_kv(k_l, k.astype(k_l.dtype), pos)
        v_l = T._scatter_kv(v_l, v.astype(v_l.dtype), pos)
        o = L.run_decode_attention(cfg, q[:, 0], k_l, v_l, pos)
        h = h + (o @ p["attn"]["wo"])[:, None, :]
        hn = L.rmsnorm_apply(p["ln2"], h, cfg.norm_eps)
        y, _ = moe_apply(p["moe"], cfg, hn, dropless=True)
        return h + y, (k_l, v_l)

    x, (k_new, v_new) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
    cache = {"k": k_new, "v": v_new, "pos": pos + 1}
    h = L.rmsnorm_apply(params["ln_f"], x, cfg.norm_eps)
    logits = L.logits_from_hidden(T.head_weights(params, cfg), cfg, h)
    return cache, logits[:, 0]


# ------------------------------------------------- paged serving (UniMem)
#
# Same page arena as the dense transformer (the attention geometry is
# identical, including the fused paged decode/prefill kernels under
# attention_impl="flash_pallas"); the MoE block runs INSIDE the paged
# dataplane — per decode step every row's token vector is routed and
# dispatched through the expert stack (grouped_matmul under
# moe_dispatch="grouped"), i.e. the paper's vector-unit sparsity on the
# serving path.

init_paged_cache = T.init_paged_cache
paged_cache_axes = T.paged_cache_axes


def _moe_ffn(p, cfg: ModelConfig, hn, valid):
    """Per-layer FFN for the paged bodies: DROPLESS expert dispatch —
    outputs are a pure per-token function, so inert batch rows and
    ragged chunk tails cannot perturb real tokens, and identical
    prompts produce identical K/V in any batch (prefix sharing and
    co-prefill page writes stay exact)."""
    del valid                      # dropless: no capacity to compete for
    y, _ = moe_apply(p["moe"], cfg, hn, dropless=True)
    return y


def paged_prefill(params, cfg: ModelConfig, chunk, arena, block_table,
                  start, chunk_len):
    """Ragged-chunk MoE prefill — `transformer.paged_prefill`'s contract
    with expert dispatch in place of the MLP."""
    x = L.embed_tokens(params["embed"], cfg, chunk["tokens"])
    return T.paged_prefill_embeds(params, cfg, x, arena, block_table,
                                  start, chunk_len, ffn_fn=_moe_ffn)


def paged_decode_step(params, cfg: ModelConfig, arena, block_table,
                      positions, tokens):
    """One fused decode step over the arena with expert dispatch per
    token.  Same contract as `transformer.paged_decode_step`."""
    return T.paged_decode_step(params, cfg, arena, block_table,
                               positions, tokens, ffn_fn=_moe_ffn)


def paged_verify(params, cfg: ModelConfig, chunk, arena, block_table,
                 start, chunk_len):
    """Speculative-verify walk with expert dispatch — contract of
    `transformer.paged_verify` (all-position logits)."""
    return T.paged_verify(params, cfg, chunk, arena, block_table,
                          start, chunk_len, ffn_fn=_moe_ffn)
