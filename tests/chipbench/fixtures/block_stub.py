"""A block with nothing of the Llama one: a bigram table (each token's
logits are a row of one V x V matrix, read at the token before). The
tests copy it into a checkout's `blocks/` to show that the harness runs
the comparison and the count readers through whatever block a
configuration names. No program serves it, so it has no ModelConfig."""
from __future__ import annotations

import numpy as np

KV_BYTES = 1        # one byte a token: the previous token's id


def model_config(config: dict, engine: dict):
    raise NotImplementedError("no program serves the stub block")


def dims(config: dict) -> dict:
    return dict(V=config["vocab_size"], L=1)


def kv_token_bytes(D: dict) -> int:
    return KV_BYTES


def make_weights(config: dict, seed: int, sharding=None):
    V = config["vocab_size"]
    rng = np.random.default_rng(seed)
    return {"table": rng.standard_normal((V, V)).astype(np.float32)}


def served_gaps(params, D: dict, pairs, *, max_seq: int, max_new: int,
                control: bool = False):
    """Per served token, the best logit less the served token's, both
    read in the row of the token before it; the control reads the same
    row rounded to whole numbers."""
    table = params["table"]
    out = []
    for prompt, served in pairs:
        prev = np.asarray(([prompt[-1]] + list(served))[:-1])
        rows = table[prev]
        gap = rows.max(-1) - rows[np.arange(len(served)), served]
        rounded = np.round(rows).argmax(-1)
        gap8 = rows.max(-1) - rows[np.arange(len(served)), rounded]
        out.append((gap, gap8 if control else gap))
    return out


def model_flops(D: dict, prefill_rows, decode_positions) -> float:
    """One row of V logits a token."""
    tokens = sum(n for _, n in prefill_rows) + len(decode_positions)
    return float(D["V"] * tokens)


def decode_kernel_cost(D: dict, positions, kv_bytes: int,
                       act_bytes: int = 2) -> tuple[float, float]:
    return 0.0, float(len(positions) * kv_bytes)


def prefill_kernel_cost(D: dict, rows, kv_bytes: int,
                        act_bytes: int = 2) -> tuple[float, float]:
    return 0.0, float(sum(n for _, n in rows) * kv_bytes)
