"""The model under test: its configuration and its weights.

The weights are the benchmark's input, not the program's: they are drawn
here, on the device, in one jitted call from the seed, in the layout the
program serves (checked against the program's own shapes) and in bf16.
The float32 reference reads the same arrays."""
from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


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


def seed_key(seed: int):
    """A JAX key from a seed of any size (jax.random.key keeps only the
    low 32 bits of a large int)."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words)


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


def check_layout(params, cfg) -> None:
    """The weights must have exactly the program's parameter shapes."""
    from repro.models import registry
    fam = registry.get_family(cfg)
    want = jax.eval_shape(lambda k: fam.init(k, cfg), jax.random.key(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if got != want:
        raise ValueError(f"weights do not match the program's layout:\n"
                         f"made {got}\nprogram {want}")
