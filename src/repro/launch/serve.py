"""Serving driver: paged-native continuous batching on the UniMem arena.

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
        --reduced --requests 16 --max-new 24 [--layout paged|contiguous] \
        [--shards N] [--temperature T --top-k K --top-p P --sample-seed S] \
        [--kv-dtype int8] [--host-tier-pages N --high-watermark F] \
        [--prefix-cache --shared-prefix 64] [--speculate 4 --draft self:1] \
        [--port 8400 --host 0.0.0.0 --tenant-budget alpha:3,beta:1]

`--port` switches the driver from the synthetic batch loop to the
NETWORK FRONT (serve/frontend): the same engine serves HTTP + SSE
clients until interrupted — submit with `examples/serve_lm.py
--connect host:port` or any HTTP client speaking the wire schema
(frontend/protocol.py).  `--tenant-budget name:weight,...` turns on
per-tenant weighted max-min token-budget shares inside the tick.

Sampling flags build per-request `SamplingParams` (serve/sampling.py)
executed INSIDE the jitted step — each request gets its own seed
(base + uid), so reruns are reproducible while requests decorrelate.

Spins up a reduced (or full, on real hardware) model, submits a synthetic
request stream with mixed prompt lengths (vlm arches get synthetic patch
embeddings), runs the engine to completion and prints
latency/throughput/pool stats including the paged arena's page
high-water mark (the memory the layout actually ties down).  Every
decode family except pure-SSM defaults to the paged layout (dense, moe,
hybrid, vlm); ssm falls back to contiguous automatically.

`--speculate K` turns on speculative decode (serve/speculative.py): a
cheap draft model proposes K tokens per window and the target scores
the whole window in ONE batched paged-verify call; acceptance is an
exact match against the target's own counter-keyed draw, so tokens are
byte-identical to plain decode and the flag is purely a throughput
knob.  `--draft` picks the proposer: `self:N` (default `self:1`)
reuses the target's first N layers + shared embeddings/head; a
registry name (e.g. `mamba2-130m`) runs a paired small model.

`--shards N` serves from the near-memory SHARDED arena on an N-device
"mem" mesh (pages resident per chip, queries broadcast, softmax
summaries merged): on real multi-chip hosts this is the multi-chip
serving path; on CPU force host devices first with
XLA_FLAGS=--xla_force_host_platform_device_count=N.
"""
from __future__ import annotations

import argparse

import numpy as np
import jax

from repro.configs import get_arch
from repro.models.config import reduced_for_smoke
from repro.models import registry
from repro.serve import ServingEngine, Request, SamplingParams
from repro.utils.compile_cache import use_compile_cache
from repro.utils.logging import get_logger

log = get_logger("serve")


def parse_tenant_budget(spec: str | None) -> dict[str, float] | None:
    """'alpha:3,beta:1' -> {'alpha': 3.0, 'beta': 1.0}; '' / None -> None.
    A bare name means weight 1.0."""
    if not spec:
        return None
    out: dict[str, float] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, w = part.partition(":")
        try:
            out[name.strip()] = float(w) if w else 1.0
        except ValueError:
            raise SystemExit(f"--tenant-budget: bad weight in {part!r}")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--layout", default=None,
                    choices=["paged", "contiguous"],
                    help="default: paged where the family supports it")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="prompt tokens prefilled per engine step (paged)")
    ap.add_argument("--shards", type=int, default=1,
                    help="shard the paged arena over an N-device 'mem' "
                         "mesh (near-memory serving; needs N devices)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-request sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="per-request top-k cutoff (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="per-request nucleus mass (1.0 = off)")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="base sampling seed (request uid is added)")
    ap.add_argument("--kv-dtype", default=None,
                    choices=["bf16", "int8", "fp8"],
                    help="page-arena storage dtype: int8/fp8 quantize "
                         "K/V on write (per-page scales) and dequantize "
                         "inside the attention kernels")
    ap.add_argument("--host-tier-pages", type=int, default=None,
                    help="host-DRAM cold tier capacity in pages: "
                         "preempted sequences spill there and restore "
                         "on readmission instead of recomputing (paged "
                         "layout only)")
    ap.add_argument("--high-watermark", type=float, default=None,
                    help="pool fraction above which the engine "
                         "proactively preempts youngest slots")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="keep full prompt pages alive after their "
                         "request retires (serve/prefix_store.py): later "
                         "requests sharing the prefix adopt the cached "
                         "pages instead of re-prefilling; idle entries "
                         "are evicted LRU under memory pressure")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many SHARED system-prompt tokens "
                         "to every request (makes --prefix-cache hits "
                         "visible in stats()['prefix_store'])")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="speculative decode: draft K tokens per window, "
                         "verify them in one batched paged-prefill call "
                         "(tokens stay byte-identical to plain decode; "
                         "paged layout only)")
    ap.add_argument("--draft", default="self:1",
                    help="draft model for --speculate: 'self:N' (first N "
                         "target layers, shared embeddings) or a registry "
                         "arch name, e.g. 'mamba2-130m'")
    ap.add_argument("--port", type=int, default=None,
                    help="serve the NETWORK FRONT on this port instead of "
                         "the synthetic batch loop (0 = ephemeral; runs "
                         "until interrupted)")
    ap.add_argument("--host", default="127.0.0.1",
                    help="bind address for --port")
    ap.add_argument("--tenant-budget", default=None, metavar="T:W,...",
                    help="per-tenant weighted max-min token-budget shares, "
                         "e.g. 'alpha:3,beta:1' (unnamed tenants weigh 1)")
    args = ap.parse_args(argv)
    use_compile_cache()

    spec = get_arch(args.arch)
    cfg = spec.model
    if args.reduced:
        cfg = reduced_for_smoke(cfg, max_seq=args.max_seq)
    if args.kv_dtype:
        cfg = cfg.replace(kv_dtype=args.kv_dtype)
    fam = registry.get_family(cfg)
    if fam.decode_step is None:
        raise SystemExit(f"{args.arch} is encoder-only: nothing to serve")
    patches = cfg.num_patches if cfg.frontend == "patch" else 0
    budget = args.max_seq - args.max_new - patches
    if budget < 5:       # before params init: fail fast on full models
        raise SystemExit(
            f"--max-seq {args.max_seq} too small: {patches} patch rows + "
            f"--max-new {args.max_new} leave no room for a prompt "
            f"(need max_seq >= {patches + args.max_new + 5})")

    mesh = None
    if args.shards > 1:
        from repro.launch.mesh import make_mem_mesh
        if jax.device_count() < args.shards:
            raise SystemExit(
                f"--shards {args.shards} needs that many devices, have "
                f"{jax.device_count()} (XLA_FLAGS="
                f"--xla_force_host_platform_device_count={args.shards})")
        mesh = make_mem_mesh(args.shards)
    params = fam.init(jax.random.key(args.seed), cfg)

    if args.port is not None:
        # network-front mode: same engine, served over HTTP + SSE until
        # interrupted (serve/frontend); clients connect with
        # examples/serve_lm.py --connect host:port
        import time

        from repro.serve.frontend import FrontendServer
        srv = FrontendServer(
            cfg, params, host=args.host, port=args.port,
            max_batch=args.max_batch, max_seq=args.max_seq,
            page_size=args.page_size, layout=args.layout,
            prefill_chunk=args.prefill_chunk, mesh=mesh,
            high_watermark=args.high_watermark,
            host_tier_pages=args.host_tier_pages,
            prefix_cache=args.prefix_cache,
            speculate_k=args.speculate,
            draft=args.draft if args.speculate else None,
            tenant_weights=parse_tenant_budget(args.tenant_budget))
        srv.start()
        log.info("serving %s over http://%s:%d (tenants: %s) — Ctrl-C "
                 "to stop", args.arch, srv.host, srv.port,
                 args.tenant_budget or "off")
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            log.info("stopping: %s", srv.llm.stats)
            srv.stop()
        return []

    engine = ServingEngine(cfg, params, max_batch=args.max_batch,
                           max_seq=args.max_seq, page_size=args.page_size,
                           layout=args.layout,
                           prefill_chunk=args.prefill_chunk, mesh=mesh,
                           high_watermark=args.high_watermark,
                           host_tier_pages=args.host_tier_pages,
                           prefix_cache=args.prefix_cache,
                           speculate_k=args.speculate,
                           draft=args.draft if args.speculate else None)
    rng = np.random.default_rng(args.seed)
    if args.shared_prefix >= budget:
        raise SystemExit(f"--shared-prefix {args.shared_prefix} leaves no "
                         f"room for a per-request tail (budget {budget})")
    system = rng.integers(0, cfg.vocab_size,
                          (args.shared_prefix,)).astype(np.int32)
    for i in range(args.requests):
        plen = int(rng.integers(4, budget - args.shared_prefix))
        prompt = rng.integers(0, cfg.vocab_size, (plen,)).astype(np.int32)
        prompt = np.concatenate([system, prompt])
        pe = (rng.standard_normal((patches, cfg.frontend_dim))
              .astype(np.float32) if patches else None)
        engine.submit(Request(
            uid=i, prompt=prompt, patch_embeds=pe,
            sampling=SamplingParams(
                temperature=args.temperature, top_k=args.top_k,
                top_p=args.top_p, seed=args.sample_seed + i,
                max_new_tokens=args.max_new)))

    results = engine.run()
    lat = sorted(r.latency_s for r in results)
    mode = ("greedy" if args.temperature == 0.0 else
            f"T={args.temperature} k={args.top_k} p={args.top_p}")
    log.info("served %d requests (%s); latency p50 %.3fs p95 %.3fs; "
             "stats=%s", len(results), mode, lat[len(lat) // 2],
             lat[int(len(lat) * 0.95)], engine.stats())
    return results


if __name__ == "__main__":
    main()
