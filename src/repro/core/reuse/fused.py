"""Fused device-binned reuse profiles (ISSUE-5 tentpole, part 2).

The exact profile path materializes every window's distances host-side
and folds them through ``np.unique`` — fine as the oracle, wasteful as
the hot path.  Here the distance stream stays on device: each window's
distances (the int32 array the Fenwick scan produced) feed the
``kernels/reuse_hist`` Pallas histogram directly, accumulated in a
donated ``[2, NUM_BINS]`` buffer — row 0 the per-bin weighted counts,
row 1 the per-bin weighted distance mass.  Only the final 2x64 floats
ever cross back to the host, where they become a log2-binned
:class:`~repro.core.reuse.profile.ReuseProfile` whose bin
representative is the weighted-mean distance of the bin (the same
*representative convention* as
:func:`~repro.core.reuse.profile.log2_binned`; SDCM accuracy is
preserved — measured well under 1e-3 absolute on the validation
matrix).

Bin layout is the kernel's (:func:`repro.kernels.reuse_hist.reuse_hist
._bin_ids`), NOT ``log2_binned``'s: bin 0 holds the D = inf
(first-touch) mass, bin b >= 1 holds finite D with
``1 + floor(log2(max(D, 1))) == b``, clamped to ``NUM_BINS - 1`` — in
particular D = 0 and D = 1 share bin 1, where ``log2_binned`` gives
D = 0 its own bin.  The merge is SDCM-neutral for every
set-associative level (P(h|D) = 1 exactly for both D = 0 and D = 1
whenever assoc >= 2), so the two binnings agree at the hit-rate level
even though their histograms differ; don't diff them bin-for-bin.

On the CPU backend the Pallas call runs in interpret mode (same kernel
body, traced into XLA); on TPU the identical code compiles natively
(:func:`repro.kernels.interpret_mode`).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.reuse_hist import reuse_histogram_moments
from repro.kernels.reuse_hist.reuse_hist import NUM_BINS

from .distance import (
    DEFAULT_WINDOW,
    INF_RD,
    reuse_distance_windows_device,
)
from .profile import ReuseProfile, profile_from_pairs

__all__ = [
    "BINNED_TOLERANCE",
    "FusedReuseHistogram",
    "binned_profile_from_distances",
    "binned_profile_windows",
    "profile_from_binned_hist",
]


#: Declared accuracy of binned profiles: SDCM hit rates from them stay
#: within this absolute distance of the exact-profile rates (gated by
#: ``repro.validate`` and ``chip_smoke.py``).
BINNED_TOLERANCE = 1e-3


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("interpret",))
def _accumulate(hist, d, w, *, interpret: bool):
    return hist + reuse_histogram_moments(d, w, interpret=interpret)


class FusedReuseHistogram:
    """Streaming device accumulator for binned reuse profiles.

    ``update`` takes any distance array (device or host) and folds it
    into the donated ``[2, NUM_BINS]`` device buffer; ``profile()``
    performs the only device->host transfer.
    """

    def __init__(self, *, interpret: bool | None = None):
        self.interpret = interpret_mode() if interpret is None else interpret
        self._hist = jnp.zeros((2, NUM_BINS), jnp.float32)

    def update(self, d, w=None) -> "FusedReuseHistogram":
        d = jnp.asarray(d)
        if d.size == 0:
            return self
        if w is None:
            w = jnp.ones(d.shape, jnp.float32)
        self._hist = _accumulate(
            self._hist, d, jnp.asarray(w), interpret=self.interpret
        )
        return self

    def histogram(self) -> np.ndarray:
        return np.asarray(self._hist, dtype=np.float64)

    def profile(self) -> ReuseProfile:
        return profile_from_binned_hist(self.histogram())


def _bin_bounds(b: int) -> tuple[int, int]:
    """Inclusive [lo, hi] finite-distance range of bin b >= 1."""
    if b == 1:
        return 0, 1
    lo = 1 << (b - 1)
    if b == NUM_BINS - 1:  # top bin is clamped open-ended
        return lo, np.iinfo(np.int64).max
    return lo, (1 << b) - 1


def profile_from_binned_hist(hist: np.ndarray) -> ReuseProfile:
    """[2, NUM_BINS] count/mass histogram -> log2-binned ReuseProfile.

    Bin representatives are the per-bin weighted-mean distances
    (rounded, clamped into the bin — ``log2_binned``'s representative
    convention, over the kernel's bin layout; see the module
    docstring); bin 0 becomes the ``INF_RD`` bucket.  Counts are
    rounded to integers — the pipeline's weights are unit reference
    counts, exact in f32 up to 2^24 per bin.
    """
    hist = np.asarray(hist, dtype=np.float64)
    counts = np.rint(hist[0]).astype(np.int64)
    mass = hist[1]
    out_d, out_c = [], []
    if counts[0] > 0:
        out_d.append(INF_RD)
        out_c.append(int(counts[0]))
    for b in range(1, NUM_BINS):
        c = int(counts[b])
        if c <= 0:
            continue
        lo, hi = _bin_bounds(b)
        rep = int(np.rint(mass[b] / c))
        out_d.append(int(np.clip(rep, lo, hi)))
        out_c.append(c)
    return profile_from_pairs(
        np.asarray(out_d, dtype=np.int64), np.asarray(out_c, dtype=np.int64)
    )


def binned_profile_from_distances(
    rds, weights=None, *, interpret: bool | None = None
) -> ReuseProfile:
    """One-shot device-binned profile of a distance array."""
    acc = FusedReuseHistogram(interpret=interpret)
    acc.update(jnp.asarray(np.asarray(rds)), weights)
    return acc.profile()


def binned_profile_windows(
    source,
    line_size: int = 1,
    *,
    window_size: int = DEFAULT_WINDOW,
    interpret: bool | None = None,
) -> ReuseProfile:
    """Streaming fused profile build: chunked Fenwick scan -> Pallas
    histogram, with every window's distances staying on device.

    The binned counterpart of ``profile_from_distances_incremental(
    reuse_distance_windows(...))`` — same trace windows, same scan
    state, but the O(N) distance stream is never copied to the host.
    """
    acc = FusedReuseHistogram(interpret=interpret)
    for rds in reuse_distance_windows_device(
        source, line_size, window_size=window_size
    ):
        acc.update(rds)
    return acc.profile()
