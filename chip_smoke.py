"""Smoke run of the paged serving path on a TPU, at full model width.

    python chip_smoke.py               # one chip
    python chip_smoke.py --chips 4     # the sharded arena over four chips

Serves internlm2-1.8b (24 layers, d_model 2048, 16 query heads over 8 KV
heads, head_dim 128, vocab 92544, bf16) with weights drawn from
`--seed`, through `ServingEngine` and the fused Pallas paged kernels:
12 greedy requests with prompts of 128-1024 tokens and 32 new tokens
each, max_batch 8, max_seq 2048, page 16.

One chip:
  * serve the requests; on the tick where every slot of the first batch
    is decoding, check both fused kernels against their `ref.py` oracles
    on the served arena and block tables, every layer, with random
    queries: |kernel - oracle| <= ATOL + RTOL * |oracle| elementwise,
    the oracle in float32 at highest matmul precision;
  * check that the compiled decode step holds the Mosaic kernel
    (`tpu_custom_call`);
  * serve the same requests again (warm) and require identical tokens;
  * hold the served tokens to a plain float32 forward pass with dense
    attention, teacher-forced on them: each must be the reference's
    greedy token or within TIE_TOL of its logit.

`--chips 4` runs only the sharded-arena path of `serve/sharded/` over a
four-chip `mem` mesh beside the single-arena path it is compared with:
both token streams must pass the float32 reference check (and the
share of identical streams is reported), pages must sit on all four
banks, the arena's banks on four distinct devices, and the sharded
decode walk (per-chip partials merged across the mesh) must match the
oracle on the served sharded arena.

Every phase raises on failure; the script exits nonzero and prints no
result without a TPU.  The last line of its output is one JSON object:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np                                             # noqa: E402
import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402
from jax.sharding import PartitionSpec as P                    # noqa: E402

from repro.configs import get_arch                             # noqa: E402
from repro.kernels.paged_attention.ops import paged_decode_attention  # noqa: E402
from repro.kernels.paged_attention.ref import paged_decode_attention_ref  # noqa: E402
from repro.kernels.paged_prefill.ops import paged_prefill_attention  # noqa: E402
from repro.kernels.paged_prefill.ref import paged_prefill_attention_ref  # noqa: E402
from repro.launch.mesh import MEM_AXIS, make_mem_mesh          # noqa: E402
from repro.models import layers, registry                      # noqa: E402
from repro.serve import (Request, SamplingParams, ServingEngine,  # noqa: E402
                         TokenEvent)
from repro.serve.serve_step import lowered_paged_hlo           # noqa: E402
from repro.serve.sharded import lowered_sharded_hlo            # noqa: E402
from repro.utils.compile_cache import use_compile_cache        # noqa: E402

ARCH = "internlm2-1.8b"
MAX_BATCH, MAX_SEQ, PAGE, MAX_NEW = 8, 2048, 16, 32
REQUESTS, PROMPT_LEN = 12, (128, 1024)
CHECK_CHUNK = 64            # query rows per sequence in the prefill check
# bf16 kernel against a float32 oracle: the kernel rounds q, k, v, the
# probabilities fed to the second matmul and its output to bf16 (relative
# step 2**-8), and outputs are averages of values of order 1
ATOL, RTOL = 1e-2, 1e-2
# a served token passes if the float32 reference ranks it within this
# many logits of its best (bf16 logits near 4 are spaced 2**-5 apart, and
# the served path carries bf16 activations through 24 layers)
TIE_TOL = 0.25


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str):
    raise SystemExit(f"chip_smoke FAILED: {msg}")


# ------------------------------------------------------------ set-up

def build_params(cfg, seed: int):
    fam = registry.get_family(cfg)
    t0 = time.perf_counter()
    params = jax.jit(fam.init, static_argnums=1)(jax.random.key(seed), cfg)
    jax.block_until_ready(params)
    nbytes = sum(a.nbytes for a in jax.tree.leaves(params))
    log(f"params: {nbytes / 1e9:.3f} GB bf16 from seed {seed} in "
        f"{time.perf_counter() - t0:.1f}s")
    return params


def make_prompts(cfg, n: int, seed: int) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1, n)
    return [rng.integers(0, cfg.vocab_size, int(k)).astype(np.int32)
            for k in lens]


# ------------------------------------------------------------ serving

def snapshot(engine) -> dict:
    """Block tables and newest positions of the decoding slots — every
    position below `pos + 1` of a row is written in the arena."""
    bt = np.full((engine.max_batch, engine.max_pages), engine.arena.null_page,
                 np.int32)
    pos = np.zeros((engine.max_batch,), np.int32)
    for i, s in engine.slots.items():
        bt[i, :len(s.pages.pages)] = s.pages.pages
        pos[i] = s.pages.num_tokens - 1
    return dict(bt=bt, pos=pos)


def serve(cfg, params, prompts, *, mesh=None, on_full_batch=None) -> dict:
    """Serve `prompts` greedily through `ServingEngine`; call
    `on_full_batch(engine)` once, on the first tick where every slot of
    the first batch is decoding."""
    engine = ServingEngine(cfg, params, max_batch=MAX_BATCH,
                           max_seq=MAX_SEQ, page_size=PAGE, mesh=mesh)
    t0 = time.perf_counter()
    for uid, p in enumerate(prompts):
        engine.submit(Request(uid=uid, prompt=p,
                              sampling=SamplingParams(max_new_tokens=MAX_NEW)))
    tokens: dict[int, list[int]] = {uid: [] for uid in range(len(prompts))}
    first_token_at = None
    called = on_full_batch is None
    for ev in engine.stream():
        if isinstance(ev, TokenEvent):
            tokens[ev.uid].append(ev.token)
            if first_token_at is None:
                first_token_at = time.perf_counter()
        if (not called and len(engine.slots) == MAX_BATCH
                and all(s.generated and not s.prefilling
                        for s in engine.slots.values())):
            called = True
            on_full_batch(engine)
    wall = time.perf_counter() - t0
    if not called:
        fail("the engine never held a full batch of decoding slots")
    for uid, toks in tokens.items():
        if len(toks) != MAX_NEW or not all(0 <= t < cfg.vocab_size
                                           for t in toks):
            fail(f"request {uid}: {len(toks)} tokens, expected {MAX_NEW} "
                 f"ids below {cfg.vocab_size}")
    n_tok = sum(map(len, tokens.values()))
    log(f"served {len(prompts)} requests, {n_tok} tokens in {wall:.2f}s "
        f"(first token after {first_token_at - t0:.2f}s, "
        f"{(time.perf_counter() - first_token_at) / n_tok * 1e3:.1f} ms "
        f"wall per token after it; {engine.steps} ticks)")
    return dict(tokens=tokens, engine=engine)


# ------------------------------------------------- kernels vs oracles

def _exceed(out, ref):
    """(max |out - ref|, max of |out - ref| - RTOL*|ref|, whether every
    output is finite)."""
    out, ref = out.astype(jnp.float32), ref.astype(jnp.float32)
    err = jnp.abs(out - ref)
    return jnp.max(err), jnp.max(err - RTOL * jnp.abs(ref)), \
        jnp.all(jnp.isfinite(out))


def _queries(cfg, seed: int, b: int):
    kd, kp = jax.random.split(jax.random.key(seed + 1))
    qd = jax.random.normal(kd, (b, cfg.num_heads, cfg.head_dim), jnp.bfloat16)
    qp = jax.random.normal(kp, (b, CHECK_CHUNK, cfg.num_heads, cfg.head_dim),
                           jnp.bfloat16)
    return qd, qp


def _f32(*xs):
    return [x.astype(jnp.float32) for x in xs]


@partial(jax.jit, static_argnames="ppb")
def kernel_errors(kk, vv, l, qd, qp, bt, pos, start, clen, *, ppb: int):
    """Both fused kernels and their float32 oracles on layer `l` of the
    arena: `_exceed` of decode, then of prefill."""
    k = jax.lax.dynamic_index_in_dim(kk, l, 0, keepdims=False)
    v = jax.lax.dynamic_index_in_dim(vv, l, 0, keepdims=False)
    dec = paged_decode_attention(qd, k, v, bt, pos, pages_per_block=ppb)
    pre = paged_prefill_attention(qp, k, v, bt, start, clen,
                                  pages_per_block=ppb)
    with jax.default_matmul_precision("highest"):
        dec_ref = paged_decode_attention_ref(*_f32(qd, k, v), bt, pos)
        pre_ref = paged_prefill_attention_ref(*_f32(qp, k, v), bt, start,
                                              clen)
    return _exceed(dec, dec_ref), _exceed(pre, pre_ref)


@partial(jax.jit, static_argnames=("cfg", "mesh"))
def sharded_walk_errors(kk, vv, l, qd, bt, phys, pos, *, cfg, mesh):
    """`_exceed` of the sharded decode walk on layer `l` of the banked
    arena — each chip over its resident pages in partials mode, the
    summaries merged across the mesh — against the float32 oracle over
    the same pages (`phys`: the table in physical slots)."""
    k = jax.lax.dynamic_index_in_dim(kk, l, 0, keepdims=False)
    v = jax.lax.dynamic_index_in_dim(vv, l, 0, keepdims=False)
    scfg = cfg.replace(mem_axis=MEM_AXIS)
    walk = jax.shard_map(
        lambda q, k, v, t, p: layers.run_paged_decode_attention(
            scfg, q, k, v, t, p),
        mesh=mesh, in_specs=(P(), P(MEM_AXIS), P(MEM_AXIS), P(), P()),
        out_specs=P(), check_vma=False)
    out = walk(qd, k, v, bt, pos).reshape(qd.shape)
    with jax.default_matmul_precision("highest"):
        ref = paged_decode_attention_ref(*_f32(qd, k, v), phys, pos)
    return _exceed(out, ref)


def check_kernels(cfg, kv, snap, seed: int) -> None:
    """Both fused kernels against their oracles over the served arena,
    layer by layer: decode at each slot's newest position, prefill for
    the CHECK_CHUNK newest positions of each slot."""
    bt, pos = jnp.asarray(snap["bt"]), jnp.asarray(snap["pos"])
    start = jnp.maximum(pos + 1 - CHECK_CHUNK, 0)
    clen = jnp.minimum(pos + 1, CHECK_CHUNK)
    qd, qp = _queries(cfg, seed, bt.shape[0])

    worst = {"decode": [0.0, -1.0], "prefill": [0.0, -1.0]}
    t0 = time.perf_counter()
    for l in range(cfg.num_layers):
        for name, (err, over, finite) in zip(
                ("decode", "prefill"),
                kernel_errors(kv["k"], kv["v"], l, qd, qp, bt, pos, start,
                              clen, ppb=cfg.attn_pages_per_block)):
            if not bool(finite):
                fail(f"{name} kernel: non-finite output at layer {l}")
            worst[name][0] = max(worst[name][0], float(err))
            worst[name][1] = max(worst[name][1], float(over))
    for name, (err, over) in worst.items():
        log(f"{name} kernel vs oracle, {cfg.num_layers} layers, rows at "
            f"positions up to {int(pos.max())}: max |err| {err:.3e}, "
            f"max (|err| - {RTOL}*|ref|) {over:.3e} (limit {ATOL})")
        if over > ATOL:
            fail(f"{name} kernel disagrees with its oracle")
    log(f"kernel checks took {time.perf_counter() - t0:.1f}s")


def check_sharded_walk(cfg, engine, snap, seed: int) -> None:
    """The sharded decode walk against the oracle over the served
    sharded arena, every layer."""
    arena = engine.arena
    n, pps = arena.num_shards, arena.pages_per_shard
    bt, pos = jnp.asarray(snap["bt"]), jnp.asarray(snap["pos"])
    # global pool ids -> physical slots of the banked arena (the null
    # sentinel maps to shard 0's null slot; the position mask drops it)
    phys = jnp.where(bt == arena.null_page, pps,
                     (bt // pps) * (pps + 1) + bt % pps)
    qd, _ = _queries(cfg, seed, bt.shape[0])
    worst = [0.0, -1.0]
    for l in range(cfg.num_layers):
        err, over, finite = sharded_walk_errors(
            arena.kv["k"], arena.kv["v"], l, qd, bt, phys, pos, cfg=cfg,
            mesh=engine.mesh)
        if not bool(finite):
            fail(f"sharded walk: non-finite output at layer {l}")
        worst = [max(worst[0], float(err)), max(worst[1], float(over))]
    log(f"sharded decode walk over {n} banks vs oracle, {cfg.num_layers} "
        f"layers: max |err| {worst[0]:.3e}, max (|err| - {RTOL}*|ref|) "
        f"{worst[1]:.3e} (limit {ATOL})")
    if worst[1] > ATOL:
        fail("the sharded walk disagrees with the oracle")


def check_custom_call(hlo: str, what: str) -> None:
    n = hlo.count("tpu_custom_call")
    log(f"compiled {what}: {n} tpu_custom_call site(s)")
    if n == 0:
        fail(f"the compiled {what} holds no Pallas kernel")


# ---------------------------------------------- reference agreement

def check_against_reference(cfg, params, prompts, streams: dict) -> None:
    """Hold each served stream to the plain float32 forward pass (dense
    attention, highest matmul precision, the same bf16-valued weights),
    teacher-forced on the stream: every served token must be the
    reference's greedy token up to a near-tie, i.e. its reference logit
    within TIE_TOL of the largest.  Random weights leave many near-ties,
    so tokens are compared through the reference's logits."""
    fam = registry.get_family(cfg)
    ref_cfg = cfg.replace(dtype="float32", attention_impl="dense")
    width = PROMPT_LEN[1] + MAX_NEW

    @jax.jit
    def deficits(params, seq, at, toks):
        with jax.default_matmul_precision("highest"):
            z = fam.forward(params, ref_cfg, {"tokens": seq})[0, at]
        served = jnp.take_along_axis(z, toks[:, None], axis=1)[:, 0]
        return z.max(axis=-1) - served, jnp.argmax(z, axis=-1)

    for name, tokens in streams.items():
        worst, exact, total = 0.0, 0, 0
        for uid, p in enumerate(prompts):
            gen = np.asarray(tokens[uid], np.int32)
            seq = np.zeros((1, width), np.int32)
            seq[0, :len(p) + len(gen) - 1] = np.concatenate([p, gen[:-1]])
            at = np.arange(len(p) - 1, len(p) - 1 + len(gen), dtype=np.int32)
            gap, greedy = deficits(params, seq, at, gen)
            worst = max(worst, float(gap.max()))
            exact += int((np.asarray(greedy) == gen).sum())
            total += len(gen)
        log(f"{name}: {exact}/{total} tokens are the float32 reference's "
            f"greedy token; largest logit deficit {worst:.4f} "
            f"(limit {TIE_TOL})")
        if worst > TIE_TOL:
            fail(f"{name}: a served token is not the reference's greedy "
                 f"token, nor a near-tie of it")


def stream_agreement(a: dict, b: dict) -> tuple[float, int]:
    """(share of tokens before each stream's first divergence, streams
    identical end to end)."""
    same = ident = total = 0
    for uid in a:
        x, y = a[uid], b[uid]
        k = next((i for i, (s, t) in enumerate(zip(x, y)) if s != t), len(x))
        same += k
        ident += k == len(x) == len(y)
        total += len(x)
    return same / total, ident


# ------------------------------------------------------------ modes

def one_chip(cfg, args) -> None:
    params = build_params(cfg, args.seed)
    prompts = make_prompts(cfg, REQUESTS, args.seed)
    log(f"prompt lengths: {sorted(len(p) for p in prompts)}")

    def on_full_batch(engine):
        check_kernels(cfg, engine.arena.kv, snapshot(engine), args.seed)

    t0 = time.perf_counter()
    cold = serve(cfg, params, prompts, on_full_batch=on_full_batch)
    log(f"first serve, compiles and kernel checks included: "
        f"{time.perf_counter() - t0:.1f}s")
    del cold["engine"]
    warm = serve(cfg, params, prompts)
    del warm["engine"]
    if warm["tokens"] != cold["tokens"]:
        fail("a second serve of the same requests emitted other tokens")
    log("second serve: identical tokens")

    check_custom_call(lowered_paged_hlo(
        cfg, "decode", max_batch=MAX_BATCH, max_seq=MAX_SEQ,
        page_size=PAGE, params=params), "decode step")
    check_against_reference(cfg, params, prompts, {"served": cold["tokens"]})


def four_chips(cfg, args) -> None:
    devices = jax.devices()
    if len(devices) < 4:
        fail(f"--chips 4 needs four devices, found {len(devices)}")
    params = build_params(cfg, args.seed)
    prompts = make_prompts(cfg, REQUESTS, args.seed)
    single = serve(cfg, params, prompts)
    del single["engine"]

    mesh = make_mem_mesh(4)

    def on_full_batch(engine):
        check_sharded_walk(cfg, engine, snapshot(engine), args.seed)

    sharded = serve(cfg, params, prompts, mesh=mesh,
                    on_full_batch=on_full_batch)
    engine = sharded["engine"]
    banks = engine.pool.shard_stats()
    log("pages per bank (peak): "
        f"{[b['peak_allocated_pages'] for b in banks]}")
    if not all(b["peak_allocated_pages"] > 0 for b in banks):
        fail("some bank of the sharded arena never held a page")
    placed = {s.device.id for s in engine.arena.kv["k"].addressable_shards}
    log(f"arena banks on devices {sorted(placed)}; bytes per bank "
        f"{engine.arena.shard_kv_bytes()}")
    if len(placed) != 4:
        fail("the sharded arena is not spread over four devices")
    check_custom_call(lowered_sharded_hlo(
        cfg, mesh, "decode", max_batch=MAX_BATCH, max_seq=MAX_SEQ,
        page_size=PAGE, params=engine.params), "sharded decode step")

    del sharded["engine"], engine

    share, ident = stream_agreement(single["tokens"], sharded["tokens"])
    log(f"sharded vs single arena: {ident}/{len(prompts)} streams "
        f"identical, {share:.4f} of tokens before the first divergence")
    check_against_reference(cfg, params, prompts, {
        "single arena": single["tokens"], "sharded arena": sharded["tokens"]})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded-arena path over four chips "
                         "and the single-arena path it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    use_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"JAX found no TPU (default device: {dev.platform})")
    log(f"device: {dev.device_kind} x{len(jax.devices())}, jax "
        f"{jax.__version__}")
    cfg = get_arch(ARCH).model.replace(max_seq=MAX_SEQ)
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(cfg, args)
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"peak HBM on device 0: {stats['peak_bytes_in_use'] / 1e9:.3f} GB")
    log(f"total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
