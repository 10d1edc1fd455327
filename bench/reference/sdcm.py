"""SDCM (Brehob and Enbody; paper Eq. 1-3) as a plain array sum.

    P(h | D) = sum_{k < A} C(D, k) p^k (1 - p)^(D - k),  p = A / B

with the exact stack rule ``D < B`` for a fully associative level,
``P(h | D) = 1`` for ``D < A`` and 0 for a first touch.  ``log C(D, k)``
is built term by term, ``log C(D, k) = log C(D, k-1) + log(D-k+1) -
log k``, so every step stays in the dtype asked for: float64 for the
reference, a lower precision for the control that must fail it.
"""
from __future__ import annotations

import numpy as np

INF = -1


def phit(distances: np.ndarray, assoc: int, blocks: int,
         dtype=np.float64) -> np.ndarray:
    d = np.asarray(distances, dtype=np.int64)
    first = d == INF
    if assoc >= blocks:
        return np.where(~first & (d < blocks), 1.0, 0.0).astype(dtype)
    df = np.maximum(d, 0).astype(dtype)
    one = dtype(1.0)
    p = dtype(assoc) / dtype(blocks)
    log_p, log_q = np.log(p), np.log1p(-p)
    log_c = np.zeros_like(df)
    total = np.zeros_like(df)
    for k in range(assoc):
        if k:
            log_c = log_c + np.log(np.maximum(df - dtype(k) + one, one)) \
                - np.log(dtype(k))
        term = np.exp(log_c + dtype(k) * log_p + (df - dtype(k)) * log_q)
        total = total + np.where(df >= dtype(k), term, dtype(0.0))
    out = np.where(d <= assoc - 1, one, np.minimum(total, one))
    return np.where(first, dtype(0.0), out).astype(dtype)


def hit_rate(values: np.ndarray, counts: np.ndarray, assoc: int,
             blocks: int, dtype=np.float64) -> float:
    """Eq. 3: the profile's probabilities folded with P(h | D)."""
    total = int(np.sum(counts))
    if total == 0:
        return 0.0
    prob = (np.asarray(counts, dtype=np.float64) / total).astype(dtype)
    return float(np.sum(prob * phit(values, assoc, blocks, dtype)))


def level_geometry(level: dict) -> tuple[int, int]:
    """(effective associativity, lines) of a cache level."""
    lines = max(1, level["size_bytes"] // level["line_size"])
    return min(level["assoc"], lines), lines

