"""Least HBM bytes one call of each Pallas kernel moves, from its shapes."""
from __future__ import annotations


def gossip_mix_nodes(n: int, p: int, degree: int, itemsize: int = 4) -> int:
    """Read the (1 + D, N, P) slot stack, write the (N, P) mix."""
    return (1 + degree) * n * p * itemsize + n * p * itemsize


def abs_survival_rows(n: int, p: int, bins: int = 128) -> int:
    """Read the (N, P) float32 magnitudes, the (N, E) edges; write the counts."""
    return 4 * n * p + 2 * 4 * n * bins


def secure_mask_keyed(n: int, p: int, degree: int) -> int:
    """Read the (N, P) messages and their (N, D) keys and signs, write (N, P)."""
    return 2 * 4 * n * p + n * degree * (2 * 4 + 4)
