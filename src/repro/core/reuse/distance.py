"""Reuse (LRU stack) distance computation — paper §2.3 / §3.3.1.

The paper replaces the O(N·M) stack algorithm with a tree-based
O(N·log M) method [Niu et al., PARDA].  We implement the tree as a
Fenwick (binary-indexed) tree carried through a ``jax.lax.scan`` so the
whole pass is a single XLA program: O(N·log N) work, O(N) memory.

Conventions
-----------
* A reuse distance of ``INF_RD`` (= -1 sentinel) marks a first-touch
  (compulsory) access, the paper's ``D = ∞``.
* Distances are measured in *distinct elements* (addresses or cache
  lines) accessed strictly between two uses of the same element
  (Table 1 of the paper).
"""
from __future__ import annotations

import functools
from typing import Iterable, Iterator

import numpy as np

import jax
import jax.numpy as jnp

from repro import telemetry

INF_RD: int = -1

# Streaming-scan window default.  XLA:CPU's scan carries the Fenwick
# tree by value (one O(timeline) copy per step), so small timelines are
# faster as well as smaller; 16Ki refs balances per-step copy cost
# against per-window dispatch overhead on current CPU backends.
DEFAULT_WINDOW: int = 1 << 14

# Above this many references, reuse_distances routes to the vectorized
# offline engine (core/reuse/batched.py): bit-identical output, no
# sequential scan, and no per-trace-length XLA compilation.  Below it
# the jitted Fenwick scan is fast enough and stays the default oracle.
RD_OFFLINE_THRESHOLD: int = 1 << 13

# per_set_reuse_distances switches from the monolithic stably-
# concatenated scan (whose O(N)-per-step timeline collapses past ~50k
# refs) to the batched multi-segment engine above this size.
PER_SET_BATCH_THRESHOLD: int = 1 << 15


# ---------------------------------------------------------------------------
# Reference oracle: classic O(N·M) LRU stack (paper's "conventional" method).
# ---------------------------------------------------------------------------

def reuse_distances_ref(addresses) -> np.ndarray:
    """O(N·M) LRU-stack reuse distances.  Ground-truth oracle for tests.

    Reproduces Table 1 of the paper exactly (first touch -> INF_RD).
    """
    stack: list = []  # stack[0] is most-recently-used
    out = np.empty(len(addresses), dtype=np.int64)
    for t, a in enumerate(addresses):
        try:
            d = stack.index(a)
            out[t] = d
            stack.pop(d)
        except ValueError:
            out[t] = INF_RD
        stack.insert(0, a)
    return out


# ---------------------------------------------------------------------------
# Tree-based O(N log N) method as a single lax.scan (paper §3.3.1).
# ---------------------------------------------------------------------------

def compact_ids(addresses) -> np.ndarray:
    """Map arbitrary (possibly 64-bit) addresses to dense int32 ids."""
    arr = np.asarray(addresses)
    _, inv = np.unique(arr, return_inverse=True)
    return inv.astype(np.int32)


def _fenwick_levels(n: int) -> int:
    """Number of Fenwick iterations needed for a tree of n slots."""
    return max(1, int(n).bit_length())


@jax.jit
def _fenwick_rd_scan(ids: jnp.ndarray) -> jnp.ndarray:
    """Reuse distances over dense ids via a Fenwick tree in a lax.scan.

    The Fenwick tree stores a 1 at the (1-indexed) position of the
    *latest* occurrence of every id seen so far; the number of distinct
    ids touched in an open window (last, i) is then a prefix-sum
    difference — the Bennett–Kruskal formulation used by tree-based RD
    algorithms.
    """
    n = ids.shape[0]
    tree_size = n + 2
    levels = _fenwick_levels(tree_size)

    def query(tree, k):
        # prefix sum over 1-indexed positions 1..k; tree[0] is always 0.
        def body(_, state):
            s, k = state
            valid = k > 0
            s = s + jnp.where(valid, tree[jnp.maximum(k, 0)], 0)
            k = jnp.where(valid, k - (k & -k), k)
            return s, k

        s, _ = jax.lax.fori_loop(0, levels, body, (jnp.int32(0), k))
        return s

    def update(tree, k, v):
        def body(_, state):
            tree, k = state
            valid = (k >= 1) & (k < tree_size)
            idx = jnp.where(valid, k, 0)
            tree = tree.at[idx].add(jnp.where(valid, v, 0))
            k = k + jnp.maximum(k & -k, 1)
            return tree, k

        tree, _ = jax.lax.fori_loop(0, levels, body, (tree, k))
        # tree[0] may have accumulated masked garbage-free zeros only.
        return tree

    def step(carry, x):
        tree, last_occ = carry
        i, a = x
        last = last_occ[a]
        # distinct ids at 0-indexed positions (last, i) exclusive
        #  == ones at 1-indexed positions [last+2, i] == Q(i) - Q(last+1)
        rd = query(tree, i) - query(tree, last + 1)
        rd = jnp.where(last < 0, jnp.int32(INF_RD), rd)
        tree = jax.lax.cond(
            last >= 0,
            lambda t: update(t, last + 1, jnp.int32(-1)),
            lambda t: t,
            tree,
        )
        tree = update(tree, i + 1, jnp.int32(1))
        last_occ = last_occ.at[a].set(i)
        return (tree, last_occ), rd

    tree0 = jnp.zeros((tree_size,), dtype=jnp.int32)
    last0 = jnp.full((n,), -1, dtype=jnp.int32)
    xs = (jnp.arange(n, dtype=jnp.int32), ids)
    (_, _), rds = jax.lax.scan(step, (tree0, last0), xs)
    return rds


def reuse_distances(addresses, line_size: int = 1, *,
                    method: str = "auto") -> np.ndarray:
    """Reuse distances of a trace, optionally at cache-line granularity.

    ``line_size > 1`` maps addresses to lines first (cache prediction
    operates on line reuse, paper §3.3.2).

    ``method`` selects the exact engine — all three are bit-identical:
    ``"scan"`` is the jitted Fenwick ``lax.scan`` (the §3.3.1 oracle),
    ``"offline"`` the vectorized order-statistics pass
    (:mod:`.batched`), and ``"auto"`` (default) routes traces larger
    than :data:`RD_OFFLINE_THRESHOLD` offline, where the monolithic
    scan's O(N)-per-step timeline copy collapses its throughput.
    """
    if method not in ("auto", "scan", "offline"):
        raise ValueError(f"unknown reuse-distance method: {method}")
    arr = np.asarray(addresses, dtype=np.int64)
    if arr.size == 0:
        return np.empty(0, dtype=np.int64)
    if method == "auto":
        method = "offline" if arr.size >= RD_OFFLINE_THRESHOLD else "scan"
    with telemetry.span("reuse.distance", n=arr.size, method=method):
        if line_size > 1:
            arr = arr // line_size
        if method == "offline":
            from .batched import reuse_distances_offline

            return reuse_distances_offline(arr)
        ids = compact_ids(arr)
        return np.asarray(_fenwick_rd_scan(jnp.asarray(ids)), dtype=np.int64)


def split_by_set(
    addresses, *, line_size: int, num_sets: int
) -> tuple[list[np.ndarray], np.ndarray]:
    """Stable per-set decomposition of a trace.

    Returns the per-set line-id segments (sets in ascending order,
    program order preserved within each set) and the stable sort
    ``order`` mapping concatenated segment positions back to original
    trace positions (``out[order] = concat(per_segment_results)``).
    Shared by the per-set distance paths and the profile benchmark so
    the decomposition can never drift between them.
    """
    arr = np.asarray(addresses, dtype=np.int64)
    lines = arr // line_size
    sets = lines % num_sets
    order = np.argsort(sets, kind="stable")
    cuts = np.flatnonzero(np.diff(sets[order])) + 1
    return np.split(lines[order], cuts), order


def per_set_reuse_distances(
    addresses, *, line_size: int, num_sets: int, method: str = "auto"
) -> np.ndarray:
    """Per-set reuse distances for set-associative LRU simulation.

    An access hits a ``A``-way set-associative LRU cache iff the number
    of *distinct same-set lines* touched since the last use of its line
    is < A.  The per-set subtraces are independent, which makes this
    the canonical batched workload:

    * ``method="monolithic"`` stably concatenates the subtraces and
      runs ONE global Fenwick scan (within the reordered trace, the
      window between two occurrences of a line contains only same-set
      accesses) — exact, but the O(N) timeline makes each scan step
      cost O(N) on XLA:CPU;
    * ``method="batched"`` hands each set's subtrace to
      :func:`repro.core.reuse.batched.reuse_distances_batched`, which
      scans whole shape buckets of sets in parallel per dispatch;
    * ``"auto"`` (default) uses the batched engine once the trace
      exceeds :data:`PER_SET_BATCH_THRESHOLD` references.

    All methods are bit-identical.
    """
    if method not in ("auto", "monolithic", "batched"):
        raise ValueError(f"unknown per-set method: {method}")
    arr = np.asarray(addresses, dtype=np.int64)
    if arr.size == 0:
        return np.empty(0, dtype=np.int64)
    if method == "batched" or (
        method == "auto"
        and num_sets > 1
        and arr.size >= PER_SET_BATCH_THRESHOLD
    ):
        from .batched import reuse_distances_batched

        segments, order = split_by_set(
            arr, line_size=line_size, num_sets=num_sets
        )
        rds = reuse_distances_batched(segments)
        out = np.empty(arr.size, dtype=np.int64)
        out[order] = np.concatenate(rds) if rds else np.empty(0, np.int64)
        return out
    lines = arr // line_size
    sets = lines % num_sets
    order = np.argsort(sets, kind="stable")
    ids = compact_ids(lines[order])
    rd_sorted = np.asarray(_fenwick_rd_scan(jnp.asarray(ids)), dtype=np.int64)
    out = np.empty_like(rd_sorted)
    out[order] = rd_sorted
    return out


# ---------------------------------------------------------------------------
# Streaming (checkpointed) Fenwick pass — peak memory O(window + working
# set), not O(N)  (ISSUE-2 tentpole; PARDA-style chunked scan).
# ---------------------------------------------------------------------------
#
# The in-memory pass above indexes its Fenwick tree by *absolute time*,
# so tree and last-occurrence buffers are O(N).  The streaming pass
# exploits the invariant that at any instant the tree holds exactly one
# 1 per distinct id (at its latest occurrence): reuse distances depend
# only on the *order* of those ones, not their absolute positions.  We
# therefore run the same scan over fixed-size windows appended to a
# bounded timeline, and when the timeline fills up we *compact* it —
# re-number the at-most-M live positions 0..M-1 in time order and
# rebuild the tree host-side in O(M).  Peak memory is O(timeline) =
# O(window + distinct lines), independent of trace length, and the
# emitted distances are bit-identical to the monolithic pass.
#
# The per-window scan carries ``(tree, last_slot)`` as donated jit
# buffers, so consecutive windows update device state in place instead
# of allocating fresh O(timeline) arrays each call.


class _IdMap:
    """Incremental address -> dense int32 id map (vectorized)."""

    def __init__(self):
        self._keys = np.empty(0, dtype=np.int64)   # sorted known addresses
        self._ids = np.empty(0, dtype=np.int32)    # id of each sorted key
        self.n = 0

    def map(self, keys: np.ndarray) -> np.ndarray:
        pos = np.searchsorted(self._keys, keys)
        hit = np.zeros(len(keys), dtype=bool)
        in_range = pos < self._keys.size
        hit[in_range] = self._keys[pos[in_range]] == keys[in_range]
        new = np.unique(keys[~hit])
        if new.size:
            ins = np.searchsorted(self._keys, new)
            self._keys = np.insert(self._keys, ins, new)
            self._ids = np.insert(
                self._ids, ins,
                np.arange(self.n, self.n + new.size, dtype=np.int32),
            )
            self.n += int(new.size)
            # Fix up the already-computed positions instead of re-running
            # a full searchsorted over all known keys: a key's index in
            # the merged array is its index among the old keys plus the
            # number of new keys sorting strictly before it — and for a
            # new key the 'left' search over ``new`` is exactly its own
            # insertion rank, so one small search covers both cases.
            pos = pos + np.searchsorted(new, keys)
        return self._ids[pos]


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 1).bit_length()


def _fenwick_from_ones_prefix(num_ones: int, cap: int) -> np.ndarray:
    """Fenwick tree over ``cap`` slots with 1s at 1-indexed 1..num_ones.

    O(cap) vectorized construction: tree[i] covers (i - lowbit(i), i],
    and the prefix count of a 1..m ones block is min(i, m).
    """
    idx = np.arange(cap, dtype=np.int64)
    low = idx & -idx
    tree = np.minimum(idx, num_ones) - np.minimum(idx - low, num_ones)
    tree[0] = 0
    return tree.astype(np.int32)


@functools.lru_cache(maxsize=None)
def _window_scan_fn(cap: int):
    """Jitted one-window Fenwick scan over a ``cap``-slot timeline.

    Cached per timeline capacity; ``tree`` and ``last_slot`` are donated
    so repeated windows reuse the same device buffers.  The step body is
    tuned for XLA:CPU scan throughput: both prefix queries run through
    ONE unrolled descent on a length-2 index vector, and the two point
    updates (+1 at the new position, -1 at the stale one) land in ONE
    2-element scatter-add per Fenwick level.
    """
    levels = _fenwick_levels(cap)

    def query2(tree, k2):
        # prefix sums at two 1-indexed positions simultaneously
        s2 = jnp.zeros((2,), dtype=jnp.int32)
        for _ in range(levels):
            valid = k2 > 0
            s2 = s2 + jnp.where(valid, tree[jnp.maximum(k2, 0)], 0)
            k2 = jnp.where(valid, k2 - (k2 & -k2), k2)
        return s2

    def update2(tree, k2, v2):
        # climb both update paths together; masked lanes write 0 to
        # tree[0], which query2 never reads
        for _ in range(levels):
            valid = (k2 >= 1) & (k2 < cap)
            idx = jnp.where(valid, k2, 0)
            tree = tree.at[idx].add(jnp.where(valid, v2, 0))
            k2 = k2 + jnp.maximum(k2 & -k2, 1)
        return tree

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def run(tree, last_slot, ids, base_slot):
        def step(carry, x):
            tree, last_slot = carry
            j, a = x
            slot = base_slot + j
            last = last_slot[a]
            q = query2(tree, jnp.stack([slot, last + 1]))
            rd = jnp.where(last < 0, jnp.int32(INF_RD), q[0] - q[1])
            seen = last >= 0
            k2 = jnp.stack([slot + 1, jnp.where(seen, last + 1, 0)])
            v2 = jnp.stack(
                [jnp.int32(1), jnp.where(seen, jnp.int32(-1), 0)]
            )
            tree = update2(tree, k2, v2)
            last_slot = last_slot.at[a].set(slot)
            return (tree, last_slot), rd

        n = ids.shape[0]
        xs = (jnp.arange(n, dtype=jnp.int32), ids)
        (tree, last_slot), rds = jax.lax.scan(step, (tree, last_slot), xs)
        return tree, last_slot, rds

    return run


def iter_address_windows(
    source, *, window_size: int = DEFAULT_WINDOW, line_size: int = 1
) -> Iterator[np.ndarray]:
    """Normalize any trace-like input into int64 line-id windows.

    Accepts a ``ChunkedTraceSource`` (anything with ``.windows()``,
    including ``LabeledTrace``), a flat address array, or an iterable of
    already-windowed pieces (``LabeledTrace`` windows or arrays).
    """
    if hasattr(source, "windows"):
        pieces: Iterable = source.windows(window_size)
    elif isinstance(source, np.ndarray) or (
        isinstance(source, (list, tuple))
        and (
            len(source) == 0
            or (
                not hasattr(source[0], "addresses")
                and np.ndim(source[0]) == 0
            )
        )
    ):
        arr = np.asarray(source, dtype=np.int64)
        pieces = (
            arr[i: i + window_size] for i in range(0, arr.size, window_size)
        )
    else:  # an iterator/iterable of windows
        pieces = source
    for piece in pieces:
        a = piece.addresses if hasattr(piece, "addresses") else piece
        a = np.asarray(a, dtype=np.int64)
        if line_size > 1:
            a = a // line_size
        yield a


def reuse_distance_windows(
    source,
    line_size: int = 1,
    *,
    window_size: int = DEFAULT_WINDOW,
) -> Iterator[np.ndarray]:
    """Yield per-window reuse distances of a (possibly huge) trace.

    Bit-identical, window-by-window, to ``reuse_distances`` over the
    concatenated trace; peak memory is O(window + distinct lines).  Feed
    the windows to ``profile_from_distances_incremental`` to build a
    :class:`ReuseProfile` without ever materializing the O(N) distance
    array.
    """
    for rds in reuse_distance_windows_device(
        source, line_size, window_size=window_size
    ):
        yield np.asarray(rds, dtype=np.int64)


def reuse_distance_windows_device(
    source,
    line_size: int = 1,
    *,
    window_size: int = DEFAULT_WINDOW,
) -> Iterator[jnp.ndarray]:
    """Device-resident variant of :func:`reuse_distance_windows`.

    Yields each window's distances as the int32 device array the
    Fenwick scan produced — the fused profile path
    (:mod:`repro.core.reuse.fused`) feeds these straight into the
    ``kernels/reuse_hist`` histogram, so a streaming profile build
    never materializes distances host-side.
    """
    if window_size < 1:
        raise ValueError("window_size must be >= 1")
    idmap = _IdMap()
    last_time = np.empty(0, dtype=np.int64)  # per id: last global position
    tree = last_slot = None
    cap = id_cap = 0
    base_slot = 0
    global_pos = 0

    for awin in iter_address_windows(
        source, window_size=window_size, line_size=line_size
    ):
        w = int(awin.size)
        if w == 0:
            yield jnp.empty(0, dtype=jnp.int32)
            continue
        ids = idmap.map(awin)
        n_ids = idmap.n
        if n_ids > last_time.size:
            grown = np.full(_pow2(n_ids), -1, dtype=np.int64)
            grown[: last_time.size] = last_time
            last_time = grown
        if last_slot is not None and n_ids > id_cap:
            id_cap = _pow2(n_ids)
            pad = id_cap - last_slot.shape[0]
            last_slot = jnp.concatenate(
                [last_slot, jnp.full(pad, -1, dtype=jnp.int32)]
            )
        if tree is None or base_slot + w + 2 > cap:
            # compact: live ones renumbered 0..m-1 in time order
            seen = np.flatnonzero(last_time[:n_ids] >= 0)
            order = seen[np.argsort(last_time[seen], kind="stable")]
            m = int(order.size)
            # room for >= 2 windows past the compacted prefix, so a
            # near-full working set doesn't force per-window rebuilds
            cap = max(cap, _pow2(max(m + 2 * w + 2, 4 * window_size)))
            id_cap = max(id_cap, _pow2(n_ids))
            ls = np.full(id_cap, -1, dtype=np.int32)
            ls[order] = np.arange(m, dtype=np.int32)
            tree = jnp.asarray(_fenwick_from_ones_prefix(m, cap))
            last_slot = jnp.asarray(ls)
            base_slot = m
        run = _window_scan_fn(cap)
        tree, last_slot, rds = run(
            tree, last_slot, jnp.asarray(ids), jnp.int32(base_slot)
        )
        # host-side checkpoint: last occurrence position of each id
        rev_ids, rev_idx = np.unique(ids[::-1], return_index=True)
        last_time[rev_ids] = global_pos + (w - 1 - rev_idx)
        base_slot += w
        global_pos += w
        yield rds


def reuse_distances_streaming(
    source,
    line_size: int = 1,
    *,
    window_size: int = DEFAULT_WINDOW,
) -> np.ndarray:
    """Streaming counterpart of :func:`reuse_distances`.

    Materializes only the output; the scan state is bounded by the
    window and the working set.  Bit-identical to the in-memory pass for
    every window size (enforced by tests).
    """
    parts = list(
        reuse_distance_windows(source, line_size, window_size=window_size)
    )
    if not parts:
        return np.empty(0, dtype=np.int64)
    return np.concatenate(parts)
