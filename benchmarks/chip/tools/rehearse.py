"""Compile a cell's programs for a described (not attached) TPU v5e and
print what each holds in device memory, to size the KV arena so that
the weights, the arena and every step's temporaries fit.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/tools/rehearse.py \
        --workload <name> [--pool-pages N]

Compiles the prefill step at the largest chunk bucket, the decode step,
and (on one chip) the reference's program, at the cell's sizes; on
several chips the numbers are per device. Nothing runs."""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parents[3] / "src"))
from harness import spec as S                                  # noqa: E402

GIB = 2 ** 30


def show(name, compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    print(f"{name}: arguments {m.argument_size_in_bytes / GIB:.3f} GiB, "
          f"output {m.output_size_in_bytes / GIB:.3f} (aliased "
          f"{m.alias_size_in_bytes / GIB:.3f}), temp "
          f"{m.temp_size_in_bytes / GIB:.3f}; total {total / GIB:.3f} GiB",
          flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--pool-pages", type=int, default=None)
    ap.add_argument("--skip-reference", action="store_true")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.models import registry
    from repro.serve import serve_step
    from repro.serve.sampling import greedy_state

    parts = S.resolve(args.workload)
    eng = dict(parts["engine"])
    if args.pool_pages:
        eng["pool_pages"] = args.pool_pages
    B = parts["block"]
    cfg = B.model_config(parts["config"], eng)
    fam = registry.get_family(cfg)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    chips = parts["cell"]["chips"]
    pool = eng.get("pool_pages") or eng["max_batch"] * eng["max_seq"] // eng["page_size"]
    slots = pool + 1
    if chips > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.launch.mesh import MEM_AXIS
        mesh = Mesh(np.array(topo.devices[:chips]), (MEM_AXIS,))
        one = NamedSharding(mesh, P())
        slots = chips * (pool // chips + 1)
    params = jax.tree.map(spec, jax.eval_shape(
        lambda: fam.init(jax.random.key(0), cfg)))
    arena = jax.eval_shape(lambda: fam.init_paged_cache(
        cfg, slots, eng["page_size"], eng["max_batch"]))
    if chips > 1:
        arena = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, P(None, MEM_AXIS))),
            arena)
    else:
        arena = jax.tree.map(spec, arena)
    b = eng["max_batch"]
    mp = -(-eng["max_seq"] // eng["page_size"])
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)  # noqa
    st = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                     sharding=one),
                      greedy_state(b))
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"        # donation and the fused kernels
    try:
        if chips > 1:
            from repro.serve.sharded import make_sharded_serve_fns
            pf, df = make_sharded_serve_fns(cfg, mesh, pool)
        else:
            pf, df = serve_step.make_paged_serve_fns(cfg)
        c = max(4 * eng["page_size"], 32)     # the engine's default chunk
        show(f"prefill ({b}, {c})", pf.lower(
            params, {"tokens": i32(b, c)}, arena, i32(b, mp), i32(b),
            i32(b), st).compile())
        show(f"decode ({b})", df.lower(params, arena, i32(b, mp), i32(b),
                                       i32(b), st).compile())
    finally:
        jax.default_backend = real
    if not args.skip_reference and chips == 1:
        K = parts["traffic"]["output"]["max"]
        f = B.lower_reference(params, B.dims(parts["config"]),
                              max_seq=eng["max_seq"], max_new=K, sharding=one)
        show(f"reference (max_seq {eng['max_seq']}, K={K})", f.compile())
    tok = B.kv_token_bytes(B.dims(parts["config"]))
    print(f"pool pages {pool}: arena "
          f"{sum(np.prod(a.shape) * a.dtype.itemsize for a in jax.tree.leaves(arena)) / GIB:.3f} GiB; "
          f"{tok} B of KV a token, {pool * eng['page_size']} tokens")
    return 0


if __name__ == "__main__":
    sys.exit(main())
