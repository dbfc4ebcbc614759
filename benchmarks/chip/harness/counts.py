"""Operations and bytes the algorithm needs, from the model's sizes and
the lengths each call served. Nothing here looks at the grid, the
padding or the rows a kernel skips, so the counts stay the same
whatever kernel implements the walk.

Model FLOPs follow the usual inference accounting (launch/cells.py in
the program): 2 x matmul parameters per token, plus the attention
score and value products, 4 x heads x head_dim per key attended."""
from __future__ import annotations


def matmul_params(D: dict) -> int:
    """Parameters that multiply a token's activations: the layers' four
    attention and three MLP projections, and the output head (the
    embedding is a lookup)."""
    qd, kvd = D["hq"] * D["hd"], D["hkv"] * D["hd"]
    per_layer = D["d"] * qd + 2 * D["d"] * kvd + qd * D["d"] + 3 * D["d"] * D["ff"]
    return D["L"] * per_layer + D["d"] * D["V"]


def _keys_prefill(start: int, n: int) -> int:
    """Keys attended by n causal queries at positions start..start+n-1."""
    return n * start + n * (n + 1) // 2


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
