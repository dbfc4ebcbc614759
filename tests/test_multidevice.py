"""Multi-device behaviour (8 forced host devices, subprocess-isolated so
the main test process keeps its single real device).

Covers: sharded train step == single-device train step, GPipe pipeline ==
sequential reference, int8-compressed gradient all-reduce accuracy,
dry-run machinery end-to-end on a small mesh.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(body: str, n: int = 8, timeout: int = 560) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
        import sys
        sys.path.insert(0, {os.path.join(REPO, "src")!r})
        sys.path.insert(0, {os.path.join(REPO, "tests")!r})
    """) + textwrap.dedent(body)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=timeout, cwd=REPO)
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return proc.stdout


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from conftest import TINY, tiny_batch
        from repro.train.optimizer import OptimizerConfig, make_optimizer
        from repro.train import train_step as TS
        from repro.launch.mesh import make_mesh
        from repro.distribution.sharding import use_mesh
        from repro.launch.cells import batch_shardings
        from repro.utils.tree import tree_allclose

        cfg = TINY["dense"]
        opt = make_optimizer(OptimizerConfig(total_steps=10))
        batch = tiny_batch(cfg, batch=8, seq=32)

        def once(mesh_shape):
            mesh = make_mesh(mesh_shape, ("data", "model"))
            with use_mesh(mesh):
                sh = TS.state_shardings(cfg, opt, mesh)
                state = jax.jit(lambda k: TS.init_train_state(k, cfg, opt),
                                out_shardings=sh)(jax.random.key(0))
                step = jax.jit(TS.make_train_step(cfg, opt, grad_accum=2),
                               in_shardings=(sh, batch_shardings(
                                   jax.eval_shape(lambda: batch), mesh, None)))
                state, m = step(state, batch)
                return jax.device_get(state.params), float(m["loss"])

        p1, l1 = once((1, 1))
        p8, l8 = once((2, 4))
        assert abs(l1 - l8) < 2e-4, (l1, l8)
        assert tree_allclose(p1, p8, rtol=2e-3, atol=2e-4)
        print("sharded == single: OK", l1, l8)
    """)


@pytest.mark.slow
def test_gpipe_pipeline_matches_sequential():
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.distribution.pipeline import (
            pipelined_forward, stage_params_split, gpipe_bubble_fraction)
        from repro.launch.mesh import make_mesh

        L, S, M, mb, d = 8, 4, 6, 4, 16
        mesh = make_mesh((4,), ("stage",))
        key = jax.random.key(0)
        w = jax.random.normal(key, (L, d, d)) * (1.0 / np.sqrt(d))
        xs = jax.random.normal(jax.random.key(1), (M, mb, d))

        def layer_fn(h, wi):
            return jnp.tanh(h @ wi)

        # sequential reference
        def seq_fwd(x):
            for i in range(L):
                x = layer_fn(x, w[i])
            return x
        want = jax.vmap(seq_fwd)(xs)

        stage_p = stage_params_split({"w": w}, S)["w"]
        got = pipelined_forward(layer_fn, stage_p, xs, mesh)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        assert abs(gpipe_bubble_fraction(S, M) - 3/9) < 1e-9
        print("gpipe == sequential: OK")
    """)


@pytest.mark.slow
def test_int8_compressed_psum_close_to_exact():
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.distribution.collectives import ring_allreduce_int8
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((8,), ("pod",))
        x = jax.random.normal(jax.random.key(0), (8, 256))

        def body(xl):
            exact = jax.lax.psum(xl, "pod")
            approx = ring_allreduce_int8(xl[0], "pod")
            return exact[0], approx

        exact, approx = jax.shard_map(body, mesh=mesh, in_specs=P("pod"),
                                      out_specs=(P(), P()),
                                      check_vma=False)(x)
        err = np.abs(np.asarray(exact) - np.asarray(approx))
        rel = err.max() / np.abs(np.asarray(exact)).max()
        assert rel < 0.02, rel          # int8 wire: ~1% worst-case error
        print("int8 psum rel err:", rel)
    """)


@pytest.mark.slow
def test_dryrun_cell_on_small_mesh():
    """The dry-run machinery end-to-end (reduced arch, 2x4 mesh)."""
    run_with_devices("""
        import jax
        from repro.configs import get_arch
        from repro.models.config import reduced_for_smoke
        import dataclasses
        from repro.launch.cells import build_cell, lower_cell
        from repro.launch.mesh import make_mesh
        from repro.launch import hlo_analysis as H

        spec = get_arch("yi-9b")
        spec = dataclasses.replace(
            spec, model=reduced_for_smoke(spec.model, max_seq=4096))
        mesh = make_mesh((2, 4), ("data", "model"))
        cell = build_cell("yi-9b", "train_4k", mesh, spec=spec)
        compiled = lower_cell(cell, mesh).compile()
        ma = compiled.memory_analysis()
        assert ma.argument_size_in_bytes > 0
        s = H.summarize(compiled.as_text())
        assert s.flops > 0
        assert s.total_collective_bytes > 0   # TP matmuls must communicate
        print("dryrun small-mesh OK: flops/dev %.2e, coll %.2e" %
              (s.flops, s.total_collective_bytes))
    """)


@pytest.mark.slow
def test_moe_ep_dispatch_matches_scatter_on_mesh():
    """EP shard_map dispatch == global scatter on a real (2,4) mesh."""
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from conftest import TINY, tiny_batch
        from repro.models import registry
        from repro.distribution.sharding import use_mesh
        from repro.launch.mesh import make_mesh

        cfg = TINY["moe"].replace(capacity_factor=8.0)  # no drops
        fam = registry.get_family(cfg)
        params = fam.init(jax.random.key(8), cfg)
        batch = tiny_batch(cfg, batch=4, seq=16, seed=4)
        mesh = make_mesh((2, 4), ("data", "model"))
        with use_mesh(mesh):
            ls = jax.jit(lambda p, b: fam.forward(
                p, cfg.replace(moe_dispatch="scatter"), b))(params, batch)
            le = jax.jit(lambda p, b: fam.forward(
                p, cfg.replace(moe_dispatch="ep"), b))(params, batch)
        np.testing.assert_allclose(np.asarray(ls), np.asarray(le),
                                   rtol=3e-4, atol=3e-4)
        # gradients must flow through the shard_map dispatch identically
        with use_mesh(mesh):
            gs = jax.jit(jax.grad(lambda p: fam.loss_fn(
                p, cfg.replace(moe_dispatch="scatter"), batch)))(params)
            ge = jax.jit(jax.grad(lambda p: fam.loss_fn(
                p, cfg.replace(moe_dispatch="ep"), batch)))(params)
        for a, b in zip(jax.tree.leaves(gs), jax.tree.leaves(ge)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-4)
        print("moe EP == scatter (fwd + grad) on (2,4) mesh: OK")
    """)


@pytest.mark.slow
def test_vocab_parallel_embedding_matches_plain_lookup():
    """Masked-local shard_map lookup == plain take, fwd and grad."""
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from conftest import TINY
        from repro.models import layers as L
        from repro.distribution.sharding import use_mesh
        from repro.launch.mesh import make_mesh

        cfg = TINY["dense"]            # vocab 128 % model 4 == 0
        emb = jax.random.normal(jax.random.key(0),
                                (cfg.vocab_size, cfg.d_model))
        toks = jax.random.randint(jax.random.key(1), (4, 16), 0,
                                  cfg.vocab_size)
        plain = jnp.take(emb, toks, axis=0)
        mesh = make_mesh((2, 4), ("data", "model"))
        with use_mesh(mesh):
            got = jax.jit(lambda e, t: L.embed_tokens(e, cfg, t))(emb, toks)
        np.testing.assert_allclose(np.asarray(got), np.asarray(plain),
                                   rtol=1e-6)
        # gradient: local scatter-add must equal the dense one-hot grad
        def loss(e):
            with use_mesh(mesh):
                return (L.embed_tokens(e, cfg, toks) ** 2).sum()
        def loss_plain(e):
            return (jnp.take(e, toks, axis=0).astype(cfg.compute_dtype) ** 2).sum()
        g1 = jax.jit(jax.grad(loss))(emb)
        g2 = jax.jit(jax.grad(loss_plain))(emb)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-5)
        print("vocab-parallel embed == plain: OK")
    """)


@pytest.mark.slow
def test_stationarity_invariant_on_compiled_cell():
    """The paper's execution invariant (DESIGN.md §5) on a real compiled
    cell: collective traffic is activations (+ allowed FSDP gathers);
    parameters never move otherwise."""
    run_with_devices("""
        import dataclasses, numpy as np, jax
        from repro.configs import get_arch
        from repro.models.config import reduced_for_smoke
        from repro.models import registry
        from repro.launch.cells import build_cell, lower_cell
        from repro.launch.mesh import make_mesh
        from repro.core.dataflow import audit_stationarity
        from repro.utils.tree import tree_flatten_with_names

        spec = get_arch("yi-9b")
        spec = dataclasses.replace(
            spec, model=reduced_for_smoke(spec.model, max_seq=4096))
        mesh = make_mesh((2, 4), ("data", "model"))
        cell = build_cell("yi-9b", "train_4k", mesh, spec=spec)
        compiled = lower_cell(cell, mesh).compile()

        # per-device shard byte sizes + full sizes of every parameter
        params = cell.args[0].params
        sizes = set()
        for name, leaf in tree_flatten_with_names(params):
            full = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
            sizes.add(full)
            for div in (2, 4, 8):
                if full % div == 0:
                    sizes.add(full // div)
        rep = audit_stationarity(compiled.as_text(), param_shard_bytes=set(),
                                 fsdp_param_bytes=sizes)
        frac = rep.stationarity_fraction
        assert frac == 1.0, f"raw parameter movement detected: {frac}"
        assert rep.activation_collective_bytes > 0
        print("stationarity fraction:", frac,
              "activation bytes:", rep.activation_collective_bytes,
              "fsdp gather bytes:", rep.fsdp_gather_bytes)
    """)
