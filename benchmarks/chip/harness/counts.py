"""Counting arithmetic every block's operation and byte counts share
(`blocks/<name>.py` hold the counts themselves)."""
from __future__ import annotations


def _keys_prefill(start: int, n: int) -> int:
    """Keys attended by n causal queries at positions start..start+n-1."""
    return n * start + n * (n + 1) // 2
