"""Reuse profiles from a labeled trace, written from the paper.

* :func:`private_traces` -- Algorithm 1 (paper section 3.2): blocks
  with fewer dynamic instances than cores are copied to every core,
  the others are split evenly in instance order; every non-shared
  reference of core ``c > 0`` moves by ``c`` times an offset above the
  trace's footprint (rounded up to 4 KiB).
* :func:`round_robin` -- Algorithm 2: one reference per core in turn,
  exhausted cores skipped.
* :func:`stack_distances` -- LRU stack distances (paper Table 1; -1 for
  a first touch), counted offline: the distance of access ``t`` with
  previous use ``p`` is the number of positions ``s < t`` whose own
  previous use lies at or before ``p``, less ``p + 1``.  The count is
  a prefix query answered on a merge-sort tree of the previous-use
  array, one ``searchsorted`` per tree level.
* :func:`histogram` -- the profile: sorted distinct distances and
  their counts.
"""
from __future__ import annotations

import numpy as np

INF = -1
OFFSET_ALIGN = 4096


def instance_ranks(bb_ids: np.ndarray, inst_ids: np.ndarray
                   ) -> tuple[np.ndarray, np.ndarray]:
    """Per reference: how many instances its block has, and the rank
    of its instance among the block's instances (0-based, in order)."""
    n = len(bb_ids)
    starts = np.ones(n, dtype=bool)
    starts[1:] = inst_ids[1:] != inst_ids[:-1]
    first = np.flatnonzero(starts)
    inst_of_ref = np.cumsum(starts) - 1
    seen: dict[int, int] = {}
    rank = np.empty(len(first), dtype=np.int64)
    for i, bb in enumerate(bb_ids[first].tolist()):
        rank[i] = seen.get(bb, 0)
        seen[bb] = rank[i] + 1
    per_bb = np.array([seen[bb] for bb in bb_ids[first].tolist()],
                      dtype=np.int64)
    return per_bb[inst_of_ref], rank[inst_of_ref]


def private_traces(addresses, bb_ids, inst_ids, shared_mask,
                   cores: int) -> list[np.ndarray]:
    """Algorithm 1: the address stream of each core."""
    addresses = np.asarray(addresses, dtype=np.int64)
    if cores == 1:
        return [addresses]
    count, rank = instance_ranks(np.asarray(bb_ids), np.asarray(inst_ids))
    copy = count < cores
    per_core = np.maximum(count // cores, 1)
    owner = np.minimum(rank // per_core, cores - 1)
    span = int(addresses.max()) + 1 if len(addresses) else 1
    offset = -(-span // OFFSET_ALIGN) * OFFSET_ALIGN
    out = []
    for c in range(cores):
        sel = copy | (owner == c)
        a = addresses[sel]
        if c > 0:
            a = np.where(np.asarray(shared_mask)[sel], a, a + offset * c)
        out.append(a)
    return out


def round_robin(streams: list[np.ndarray]) -> np.ndarray:
    """Algorithm 2, round robin: position-major, core-minor order."""
    pos = np.concatenate([np.arange(len(s)) for s in streams])
    core = np.concatenate([np.full(len(s), c) for c, s in enumerate(streams)])
    return np.concatenate(streams)[np.lexsort((core, pos))]


def previous_use(keys: np.ndarray) -> np.ndarray:
    """Index of the previous access to the same key, -1 for none."""
    order = np.argsort(keys, kind="stable")
    sk = keys[order]
    prev = np.full(len(keys), -1, dtype=np.int64)
    same = sk[1:] == sk[:-1]
    prev[order[1:][same]] = order[:-1][same]
    return prev


def stack_distances(addresses, line_size: int = 1) -> np.ndarray:
    """LRU stack distance of every access (-1 on first touch)."""
    keys = np.asarray(addresses, dtype=np.int64) // line_size
    n = len(keys)
    if n == 0:
        return np.zeros(0, dtype=np.int64)
    prev = previous_use(keys)
    levels = max(1, (n - 1).bit_length())
    size = 1 << levels
    shift = 32
    vals = np.full(size, n + 1, dtype=np.int64)   # padding never counts
    vals[:n] = prev + 1                            # in [0, n]
    t = np.arange(n, dtype=np.int64)
    bound = prev + 1
    has = prev >= 0
    count = np.zeros(n, dtype=np.int64)
    block_of = np.arange(size, dtype=np.int64)
    tree = vals
    for k in range(levels + 1):
        # tree holds vals sorted within blocks of 2**k positions,
        # each key tagged with its block so one array serves all blocks
        keyed = ((block_of >> k) << shift) | tree
        if k > 0:
            keyed.sort(kind="stable")
            tree = keyed & ((1 << shift) - 1)
        sel = has & (((t >> k) & 1) == 1)
        if not sel.any():
            continue
        block = (t[sel] >> k) - 1     # the block ending at t's prefix
        idx = np.searchsorted(keyed, (block << shift) | bound[sel],
                              side="right")
        count[sel] += idx - (block << k)
    return np.where(has, count - (prev + 1), INF)


def histogram(distances: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    values, counts = np.unique(np.asarray(distances, dtype=np.int64),
                               return_counts=True)
    return values, counts.astype(np.int64)


def cell_profiles(trace: dict, cores: int, line_size: int
                  ) -> dict[str, tuple[np.ndarray, np.ndarray]]:
    """PRD (core 0's private stream) and CRD (round-robin shared
    stream) of one core count.  ``trace`` holds the arrays
    ``addresses``, ``bb_ids``, ``inst_ids`` and ``shared_mask``."""
    streams = private_traces(trace["addresses"], trace["bb_ids"],
                             trace["inst_ids"], trace["shared_mask"], cores)
    prd = histogram(stack_distances(streams[0], line_size))
    if cores == 1:
        return {"prd": prd, "crd": prd}
    crd = histogram(stack_distances(round_robin(streams), line_size))
    return {"prd": prd, "crd": crd}
