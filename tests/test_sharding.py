"""Sharding rule engine: divisibility fallback, spec resolution, and the
weight-stationarity HLO audit.  Property-style tests are parametrized
sweeps (no hypothesis dependency); meshes come from `launch.mesh`."""
from __future__ import annotations

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distribution.sharding import (
    AxisRules, DEFAULT_RULES, SEQUENCE_PARALLEL_RULES, logical_to_spec)
from repro.core.dataflow import (
    parse_shape_bytes, parse_collectives, audit_stationarity)
from repro.launch.mesh import make_abstract_mesh as abstract_mesh, make_mesh


MESH_1POD = abstract_mesh((16, 16), ("data", "model"))
MESH_2POD = abstract_mesh((2, 16, 16), ("pod", "data", "model"))
RULES = AxisRules(dict(DEFAULT_RULES))


def test_basic_resolution_single_pod():
    spec = logical_to_spec(("embed", "mlp"), (8192, 22016), MESH_1POD, RULES)
    assert spec == P("data", "model")


def test_batch_uses_pod_and_data_on_multipod():
    spec = logical_to_spec(("act_batch", "act_seq"), (256, 4096),
                           MESH_2POD, RULES)
    assert spec == P(("pod", "data"), None)


def test_divisibility_fallback_replicates():
    # kv_heads = 8 cannot shard over model=16 -> replicate that dim
    spec = logical_to_spec(("embed", "kv_heads"), (8192, 8), MESH_1POD, RULES)
    assert spec == P("data", None)


def test_axis_used_at_most_once():
    # both dims want "model"; the second must fall back to replication
    spec = logical_to_spec(("mlp", "heads"), (4096, 4096), MESH_1POD, RULES)
    assert spec == P("model", None)


def test_unknown_axis_raises():
    with pytest.raises(KeyError):
        logical_to_spec(("nonsense",), (8,), MESH_1POD, RULES)


def test_seq_parallel_rules_shard_seq():
    rules = AxisRules(dict(SEQUENCE_PARALLEL_RULES))
    spec = logical_to_spec(("act_batch", "act_seq", "act_embed"),
                           (256, 4096, 8192), MESH_1POD, rules)
    assert spec == P("data", "model", None)


@pytest.mark.parametrize("name", [k for k, v in DEFAULT_RULES.items() if v])
@pytest.mark.parametrize("dim", [1, 2, 3, 7, 15, 16, 17, 32, 96, 100, 256,
                                 1000, 4096, 65536, (1 << 20) - 1, 1 << 20])
def test_property_fallback_always_divides(dim, name):
    """For ANY size, the resolved spec's axis product divides the dim."""
    spec = logical_to_spec((name,), (dim,), MESH_2POD, RULES)
    entry = spec[0]
    if entry is None:
        return
    axes = entry if isinstance(entry, tuple) else (entry,)
    prod = int(np.prod([MESH_2POD.shape[a] for a in axes]))
    assert dim % prod == 0 and dim >= prod


@pytest.mark.parametrize("seed", range(50))
def test_property_no_mesh_axis_reused(seed):
    rng = np.random.default_rng(seed)
    names = list(rng.choice(list(DEFAULT_RULES), rng.integers(1, 5)))
    shape = tuple(int(rng.choice([1, 8, 16, 64, 256, 4096])) for _ in names)
    spec = logical_to_spec(tuple(names), shape, MESH_2POD, RULES)
    used = []
    for entry in spec:
        if entry is None:
            continue
        used.extend(entry if isinstance(entry, tuple) else (entry,))
    assert len(used) == len(set(used)), f"reused mesh axis in {spec}"


# ------------------------------------------------------------- HLO audit

def test_parse_shape_bytes():
    assert parse_shape_bytes("bf16[16,1024]") == 16 * 1024 * 2
    assert parse_shape_bytes("f32[8]") == 32
    assert parse_shape_bytes("(f32[2,2], s8[4])") == 20


def test_stationarity_audit_on_compiled_tp_matmul():
    """Megatron pair: x@W1 (column) -> @W2 (row) + psum.  The collective
    must be activation-shaped, not weight-shaped, and the audit must see
    100% stationarity."""
    devs = jax.devices()
    if len(devs) < 1:
        pytest.skip("no devices")
    mesh = make_mesh((1, 1), ("data", "model"))

    from jax.sharding import NamedSharding
    w1 = jax.ShapeDtypeStruct((64, 256), jnp.float32)
    w2 = jax.ShapeDtypeStruct((256, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 64), jnp.float32)

    def fn(x, w1, w2):
        return jax.nn.relu(x @ w1) @ w2

    sh = lambda spec: NamedSharding(mesh, spec)
    compiled = jax.jit(fn, in_shardings=(
        sh(P("data", None)), sh(P(None, "model")), sh(P("model", None))
    )).lower(x, w1, w2).compile()
    param_bytes = {64 * 256 * 4, 256 * 64 * 4, 64 * 16 * 4, 16 * 64 * 4}
    rep = audit_stationarity(compiled.as_text(), param_bytes)
    assert rep.param_collective_bytes == 0
    assert rep.stationarity_fraction == 1.0


def test_parse_collectives_finds_ops():
    hlo = '''
ENTRY %main (p: f32[8,64]) -> f32[8,64] {
  %ag = f32[8,64]{1,0} all-gather(%p), dimensions={1}
  ROOT %ar = f32[8,64]{1,0} all-reduce(%ag), to_apply=%add
}
'''
    ops = parse_collectives(hlo)
    kinds = sorted(o.kind for o in ops)
    assert kinds == ["all-gather", "all-reduce"]
    assert all(o.shape_bytes == 8 * 64 * 4 for o in ops)
