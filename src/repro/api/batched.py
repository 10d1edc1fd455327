"""Batched SDCM: the whole (target x level x cores) grid in ONE jitted
JAX call.

The per-level oracle (``sdcm.phit_given_d_np``) walks every distinct
reuse distance in a Python loop; a paper-style sweep calls it
levels x targets x core-counts times.  Here every level profile of
every grid cell is padded into one ``[G, M]`` array and a single
``vmap``-ed, jitted kernel evaluates Eq. 1–3 for all rows at once.

Per-row associativity is a *traced* scalar: the log-space binomial term
sum runs over a static ``A_MAX`` lane axis and masks ``k >= assoc``,
which keeps one compilation per (A_MAX bucket, M bucket, G bucket)
rather than one per geometry.  Fully-associative rows (the TPU VMEM
level) take the exact stack-rule branch ``P(h|D) = [D < B]``.

Evaluation is **composition-invariant**: every row's (A_MAX, M) shape
is derived from that row alone and row counts are padded to powers of
two, so the bits a profile evaluates to are identical whether it runs
in a lone single-request grid or coalesced with arbitrary other
requests (``Session.predict_many``, the ``repro.service``
microbatcher) — the property behind the service's "bit-identical to
sequential ``Session.predict``" guarantee.

The jitted programs are named for what they compute (``sdcm_grid``,
``sdcm_sweep``, ``ecm_chain``, ``sdcm_fold``), so a profiler trace's
device ops read ``jit_sdcm_sweep/...``.  Each call is wrapped in
:mod:`repro.telemetry` spans: ``sdcm.grid`` / ``sdcm.sweep`` around
the host staging, ``sdcm.dispatch`` around each padded transfer and
jitted call (``sdcm.put`` around a sweep dispatch's one packed
transfer, ``n`` its bytes; ``sdcm.eval`` around the jitted call, ``n``
the padded row-distance elements it evaluates), ``sdcm.fetch`` around
the host's wait for its result.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro import telemetry
from repro.core.reuse.distance import INF_RD
from repro.kernels import interpret_mode
from repro.kernels.f32math import exp_f32, log1p_f32, log_f32

# log-space term sums stay ~1e-7-accurate in f32 up to this many ways;
# larger set-associative geometries don't occur in Table 5 (max 20).
A_MAX_LIMIT = 64
_A_BUCKETS = (8, 16, 32, 64)


def _phit_row(d: jnp.ndarray, assoc: jnp.ndarray, blocks: jnp.ndarray,
              a_max: int) -> jnp.ndarray:
    """P(h | D) for one padded profile row; assoc/blocks are traced."""
    inf_mask = d == float(INF_RD)
    df = jnp.maximum(d, 0.0)
    p = assoc / blocks
    p = jnp.clip(p, 1e-30, 1.0 - 1e-7)

    # log/exp from repro.kernels.f32math: the TPU's own are too coarse
    d_col = df[:, None]                                   # [M, 1]
    j = jnp.arange(1, a_max, dtype=jnp.float32)           # [A-1]
    ratios = log_f32(jnp.maximum(d_col - j + 1.0, 1e-30)) - log_f32(j)
    log_comb = jnp.concatenate(
        [jnp.zeros_like(d_col), jnp.cumsum(ratios, axis=-1)], axis=-1
    )                                                     # [M, A]
    k = jnp.arange(a_max, dtype=jnp.float32)
    log_terms = log_comb + k * log_f32(p) + (d_col - k) * log1p_f32(-p)
    valid = (k < assoc) & (k <= d_col)
    # binomial terms are <= 1: summed directly, without a log round trip
    s = jnp.minimum(
        jnp.sum(jnp.where(valid, exp_f32(log_terms), 0.0), axis=-1), 1.0
    )

    out = jnp.where(df <= assoc - 1.0, 1.0, s)
    fully = jnp.where(df < blocks, 1.0, 0.0)
    out = jnp.where(assoc >= blocks, fully, out)
    return jnp.where(inf_mask, 0.0, out)


@functools.lru_cache(maxsize=None)
def _grid_fn(a_max: int):
    @jax.jit
    def sdcm_grid(d, probs, assoc, blocks):
        phit = jax.vmap(_phit_row, in_axes=(0, 0, 0, None))(
            d, assoc, blocks, a_max
        )
        return jnp.sum(probs * phit, axis=-1)

    return sdcm_grid


def _bucket(n: int, buckets=_A_BUCKETS) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(
        f"set-associativity {n} exceeds the batched kernel's "
        f"A_MAX={A_MAX_LIMIT} (fully-associative levels are fine)"
    )


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 1).bit_length()


def pack_profiles(profiles, m: int | None = None
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Pad a list of ReuseProfiles into (distances [G, M], probs [G, M]).

    Padding rows with distance 0 / probability 0 — padded entries
    contribute nothing to the Eq. 3 dot product.  ``m`` overrides the
    padded width (callers grouping rows for composition-invariant
    evaluation pass each row's own pow2 width).
    """
    if m is None:
        # round M up so repeated sweeps reuse one compiled kernel
        m = _pow2(max((len(p.distances) for p in profiles), default=1))
    d = np.zeros((len(profiles), m), dtype=np.float32)
    pr = np.zeros((len(profiles), m), dtype=np.float32)
    for g, p in enumerate(profiles):
        n = len(p.distances)
        d[g, :n] = p.distances.astype(np.float32)
        pr[g, :n] = p.probabilities.astype(np.float32)
    return d, pr


def batched_phit(d: np.ndarray, assoc: np.ndarray, blocks: np.ndarray):
    """Vectorized P(h|D): rows of distances with per-row geometry."""
    finite = [int(a) for a, b in zip(assoc, blocks) if a < b]
    a_max = _bucket(max(finite, default=1))
    phit = jax.vmap(_phit_row, in_axes=(0, 0, 0, None))(
        jnp.asarray(d, jnp.float32),
        jnp.asarray(assoc, jnp.float32),
        jnp.asarray(blocks, jnp.float32),
        a_max,
    )
    return np.asarray(phit)


def _row_shape_key(prof, assoc: int, blocks: int) -> tuple[int, int]:
    """The (a_max bucket, padded M) this row is evaluated under.

    Derived from the ROW alone — never from what else is in the call —
    so a profile's evaluated bits are identical whether it runs in a
    single-request grid or coalesced into a service batch
    (``Session.predict_many`` / ``repro.service``).  Fully-associative
    rows take the exact stack-rule branch; their lane axis is
    irrelevant, so they share the smallest bucket.
    """
    a_max = _bucket(int(assoc)) if assoc < blocks else _A_BUCKETS[0]
    return a_max, _pow2(max(len(prof.distances), 1))


def batched_hit_rates(items) -> list[dict[str, float]]:
    """Evaluate SDCM for every level of every (target, artifacts) cell
    in one vmapped+jitted call per row shape.  Returns one
    {level: hit_rate} dict per cell.

    Rows are grouped by :func:`_row_shape_key` and the row count of
    each group is padded to a power of two, so both the compiled-kernel
    set AND each row's numerics are independent of batch composition:
    coalesced results are bit-identical to per-request evaluation.
    """
    from repro.api.stages import shared_level_index

    with telemetry.span("sdcm.grid") as span:
        rows = []       # (cell index, level name, profile, assoc, blocks)
        for ci, (target, art) in enumerate(items):
            shared_idx = shared_level_index(target)
            for li, lvl in enumerate(target.levels):
                prof = art.crd if li >= shared_idx else art.prd
                rows.append(
                    (ci, lvl.name, prof, lvl.effective_assoc, lvl.num_lines)
                )
        span.count(len(rows))
        if not rows:
            return [{} for _ in items]

        groups: dict[tuple[int, int], list[int]] = {}
        for ri, (_ci, _name, prof, assoc, blocks) in enumerate(rows):
            groups.setdefault(
                _row_shape_key(prof, assoc, blocks), []).append(ri)

        rates = np.zeros(len(rows), dtype=np.float64)
        for (a_max, m), idxs in groups.items():
            d, pr = pack_profiles([rows[i][2] for i in idxs], m)
            assoc = np.array([rows[i][3] for i in idxs], dtype=np.float32)
            blocks = np.array([rows[i][4] for i in idxs], dtype=np.float32)
            with telemetry.span("sdcm.dispatch", n=len(idxs)):
                # pad G to pow2 with inert rows (probs 0) so the number
                # of compiled kernels stays bounded as batch sizes vary
                g = _pow2(len(idxs))
                if g > len(idxs):
                    pad = g - len(idxs)
                    d = np.pad(d, ((0, pad), (0, 0)))
                    pr = np.pad(pr, ((0, pad), (0, 0)))
                    assoc = np.pad(assoc, (0, pad), constant_values=1.0)
                    blocks = np.pad(blocks, (0, pad), constant_values=2.0)
                _record_signature(("grid", a_max, g, m))
                args = (jnp.asarray(d), jnp.asarray(pr),
                        jnp.asarray(assoc), jnp.asarray(blocks))
                with telemetry.span("sdcm.eval", n=g * m):
                    out = _grid_fn(a_max)(*args)
            with telemetry.span("sdcm.fetch", n=len(idxs)):
                rates[idxs] = np.asarray(out)[:len(idxs)]
        # empty-profile rows (total == 0) follow the oracle: hit rate 0
        empty = np.array([r[2].total == 0 for r in rows])
        rates = np.where(empty, 0.0, rates)

    out: list[dict[str, float]] = [{} for _ in items]
    for (ci, name, _prof, _a, _b), rate in zip(rows, rates):
        out[ci][name] = float(rate)
    return out


# --- compile accounting ------------------------------------------------------
#
# Every jit dispatch in this module lands on a cache key derived ONLY
# from static structure (A_MAX bucket, padded shapes, level count,
# chain mode) — never from batch composition or config values.  The
# signature set below mirrors those keys so sessions can assert "a warm
# sweep compiles nothing": `compile_count()` deltas feed
# `SessionStats.kernel_compiles`.

_COMPILED: set[tuple] = set()


def _record_signature(sig: tuple) -> int:
    """Record the compile-cache key a dispatch lands on; 1 if new."""
    if sig in _COMPILED:
        return 0
    _COMPILED.add(sig)
    return 1


def compile_count() -> int:
    """Number of distinct kernel compilations triggered so far."""
    return len(_COMPILED)


def compiled_signatures() -> frozenset:
    return frozenset(_COMPILED)


# --- fused config sweeps -----------------------------------------------------
#
# The batched grid above amortizes one kernel over many (workload,
# target) cells; a config *sweep* flips the axes: ONE fixed packed
# profile against C candidate hardware configs.  Geometry (assoc,
# blocks), transfer betas, level latencies and core counts are traced
# [C, L] / [C] columns of one packed device array, so the whole sweep
# — SDCM hit rates AND the ECM runtime chain from `core/incore.py` —
# is one jitted call per row shape with no per-config host
# round-trips.  C is padded to a power of two and rows are grouped by
# their per-level A_MAX-bucket tuple, keeping the compiled-kernel set
# bounded and each config's numerics bit-identical to
# `batched_hit_rates` on the same row.

# cap C*M elements per dispatch (f32 phit buffer <= 32 MiB); larger
# sweeps split into pow2-sized chunks, still one dispatch per chunk;
# the widest profiles take chunks as small as one row.
SWEEP_MAX_ELEMS = 1 << 23


@dataclasses.dataclass(frozen=True)
class DeviceProfile:
    """A reuse profile packed once and held device-resident.

    ``d``/``p`` are pow2-padded [M] f32 device arrays with exactly the
    bytes `pack_profiles` would produce for this profile, so sweep
    rates match `batched_hit_rates` bit for bit.
    """
    d: jnp.ndarray
    p: jnp.ndarray
    m: int
    total: int


def pack_profile_device(prof) -> DeviceProfile:
    d, p = pack_profiles([prof])
    return DeviceProfile(
        d=jnp.asarray(d[0]), p=jnp.asarray(p[0]),
        m=d.shape[1], total=int(prof.total),
    )


@dataclasses.dataclass(frozen=True)
class SweepGeometry:
    """Host-staged config axes for one sweep row group.

    All arrays are f32; [C, L] for per-level axes, [C] for cores.
    ``trans_beta[:, i]`` is the transfer beta of the boundary INTO
    level i+1 (RAM for the last column) — the `core/incore.py`
    convention.  ``delta`` is the per-level access latency used by the
    latency-mode chain.
    """
    assoc: np.ndarray
    blocks: np.ndarray
    trans_beta: np.ndarray
    delta: np.ndarray
    cores: np.ndarray

    def __post_init__(self):
        c, n = self.assoc.shape
        for name in ("blocks", "trans_beta", "delta"):
            if getattr(self, name).shape != (c, n):
                raise ValueError(f"geometry field {name} shape mismatch")
        if self.cores.shape != (c,):
            raise ValueError("geometry cores shape mismatch")


@dataclasses.dataclass(frozen=True)
class SweepResult:
    rates: np.ndarray            # [C, L] float64
    t_pred_s: np.ndarray | None  # [C] float64 (None without counts)
    dispatches: int              # fused-grid invocations issued
    compiles: int                # NEW kernel compilations triggered


def _rates_body(prd_d, prd_p, crd_d, crd_p, assoc, blocks,
                a_key: tuple, shared_idx: int):
    """[C, L] hit rates; level l uses the PRD below the shared level
    and the CRD at/above it, matching `AnalyticalSDCM`."""
    c = assoc.shape[0]
    cols = []
    for lv in range(len(a_key)):
        d, p = (prd_d, prd_p) if lv < shared_idx else (crd_d, crd_p)
        d2 = jnp.broadcast_to(d, (c, d.shape[0]))
        # one opaque input set per level: levels that read one profile
        # must not share their distance-only work, which would fuse
        # (and so round: XLA:CPU contracts a*b+c into FMAs) unlike the
        # same row in `batched_hit_rates`
        d2, a_col, b_col = lax.optimization_barrier(
            (d2, assoc[:, lv], blocks[:, lv])
        )
        phit = jax.vmap(_phit_row, in_axes=(0, 0, 0, None))(
            d2, a_col, b_col, a_key[lv]
        )
        cols.append(
            jnp.sum(jnp.broadcast_to(p, d2.shape) * phit, axis=-1)
        )
    return jnp.stack(cols, axis=-1)


def _chain_body(rates, trans_beta, delta, cores,
                comp_cy, lsu_cy, mem_ops, ram_delta, cycle_s,
                shared_idx: int, mode: str):
    """ECM runtime chain on device — the `core/incore.py` math
    vectorized over the config axis.

    Per-core counts are the 1/cores share; the chip-wide saturation
    term runs on UNDIVIDED counts over the boundaries at/above the
    shared level, exactly as `ecm_cycles` does on host.
    """
    n_levels = rates.shape[1]
    reach = lax.cummin(jnp.clip(1.0 - rates, 0.0, 1.0), axis=1)
    share = 1.0 / jnp.maximum(cores, 1.0)
    full_transfers = mem_ops * reach * trans_beta        # [C, L] undivided
    if mode == "latency":
        acc = jnp.broadcast_to(ram_delta, rates.shape[:1])
        for lv in reversed(range(n_levels)):
            pl = rates[:, lv]
            acc = pl * delta[:, lv] + (1.0 - pl) * acc
        core_cy = comp_cy * share + mem_ops * share * acc
    else:
        data = lsu_cy * share + share * jnp.sum(full_transfers, axis=-1)
        core_cy = jnp.maximum(comp_cy * share, data)
    start = max(shared_idx - 1, 0)
    sat = jnp.sum(full_transfers[:, start:], axis=-1)
    return jnp.maximum(core_cy, sat) * cycle_s


# A dispatch's inputs travel to the device in ONE f32 host array
# [G, 4L+6] (one transfer, not one per axis and scalar): L columns each
# of assoc, blocks, trans_beta and delta, then cores, then the five
# chain scalars broadcast down their columns.  Padded rows take the
# inert values below (cores 1.0), as `batched_hit_rates` pads.
_SWEEP_COLUMNS = (("assoc", 1.0), ("blocks", 2.0), ("trans_beta", 0.0),
                  ("delta", 0.0))
_SWEEP_SCALARS = 5   # comp_cy, lsu_cy, mem_ops, ram_delta, cycle_s


def _pack_sweep_inputs(geom: SweepGeometry, idxs: np.ndarray, g: int,
                       scalars: tuple) -> np.ndarray:
    """Stage rows ``idxs`` of ``geom`` and the chain scalars, padded to
    ``g`` rows, in the layout `_sweep_fn` unpacks."""
    n, n_levels = len(idxs), geom.assoc.shape[1]
    packed = np.empty((g, 4 * n_levels + 1 + _SWEEP_SCALARS), np.float32)
    for i, (name, pad) in enumerate(_SWEEP_COLUMNS):
        cols = slice(i * n_levels, (i + 1) * n_levels)
        packed[:n, cols] = getattr(geom, name)[idxs]
        packed[n:, cols] = pad
    packed[:n, 4 * n_levels] = geom.cores[idxs]
    packed[n:, 4 * n_levels] = 1.0
    packed[:, 4 * n_levels + 1:] = scalars
    return packed


@functools.lru_cache(maxsize=None)
def _sweep_fn(a_key: tuple, shared_idx: int, mode: str,
              with_runtime: bool):
    n_levels = len(a_key)

    @jax.jit
    def sdcm_sweep(prd_d, prd_p, crd_d, crd_p, packed):
        assoc, blocks, trans_beta, delta = (
            packed[:, i * n_levels:(i + 1) * n_levels] for i in range(4)
        )
        cores = packed[:, 4 * n_levels]
        # strong f32 scalars: the same f32 arithmetic as the weakly
        # typed Python floats they stand for
        comp_cy, lsu_cy, mem_ops, ram_delta, cycle_s = (
            packed[0, 4 * n_levels + 1 + i] for i in range(_SWEEP_SCALARS)
        )
        rates = _rates_body(
            prd_d, prd_p, crd_d, crd_p, assoc, blocks, a_key, shared_idx
        )
        if not with_runtime:
            return rates
        t = _chain_body(
            rates, trans_beta, delta, cores,
            comp_cy, lsu_cy, mem_ops, ram_delta, cycle_s,
            shared_idx, mode,
        )
        return rates, t

    return sdcm_sweep


@functools.lru_cache(maxsize=None)
def _chain_fn(n_levels: int, shared_idx: int, mode: str):
    """Runtime chain alone — consumes externally computed hit rates
    (the Pallas inner evaluator path)."""
    del n_levels  # part of the cache key; shapes carry it at trace time

    @jax.jit
    def ecm_chain(rates, trans_beta, delta, cores,
                  comp_cy, lsu_cy, mem_ops, ram_delta, cycle_s):
        return _chain_body(
            rates, trans_beta, delta, cores,
            comp_cy, lsu_cy, mem_ops, ram_delta, cycle_s,
            shared_idx, mode,
        )

    return ecm_chain


def _sweep_akey(assoc_row: np.ndarray, blocks_row: np.ndarray) -> tuple:
    """Per-level A_MAX bucket tuple for one config — `_row_shape_key`
    applied level-wise, so each (config, level) row compiles and
    evaluates exactly as it would in `batched_hit_rates`."""
    return tuple(
        _bucket(int(a)) if a < b else _A_BUCKETS[0]
        for a, b in zip(assoc_row, blocks_row)
    )


def _pad_rows(arr: np.ndarray, pad: int, value: float) -> np.ndarray:
    if pad == 0:
        return arr
    width = ((0, pad),) + ((0, 0),) * (arr.ndim - 1)
    return np.pad(arr, width, constant_values=value)


@jax.jit
def sdcm_fold(probs, phit):
    """Eq. 3 over one packed profile, summed as the vmap path sums it
    (the profile is normalized: no division by an f32 weight sum)."""
    return jnp.sum(probs * phit)


def _pallas_rates(prd: DeviceProfile, crd: DeviceProfile,
                  geom: SweepGeometry, shared_idx: int,
                  interpret: bool) -> tuple[np.ndarray, int, int]:
    """Inner evaluator on the `repro.kernels.sdcm` Pallas kernel.

    Geometry is static per Pallas compile, so configs are grouped by
    distinct (assoc, blocks) per level — one kernel call per geometry.
    A TPU-oriented path (interpret mode on the CPU); the vmap path remains
    the default.  Returns (rates, dispatches, new compiles).
    """
    from repro.kernels.sdcm import sdcm_hit_probs

    c, n_levels = geom.assoc.shape
    rates = np.zeros((c, n_levels), dtype=np.float64)
    dispatches = 0
    compiles = 0
    for lv in range(n_levels):
        prof = prd if lv < shared_idx else crd
        pairs: dict[tuple[int, int], list[int]] = {}
        for ci in range(c):
            key = (int(geom.assoc[ci, lv]), int(geom.blocks[ci, lv]))
            pairs.setdefault(key, []).append(ci)
        for (a, b), idxs in pairs.items():
            compiles += _record_signature(
                ("pallas-sdcm", a, b, prof.m, interpret)
            )
            phit = sdcm_hit_probs(prof.d, assoc=a, blocks=b,
                                  interpret=interpret)
            r = float(sdcm_fold(prof.p, phit))
            dispatches += 1
            rates[np.asarray(idxs), lv] = r
    return rates, dispatches, compiles


def sweep_grid(prd: DeviceProfile, crd: DeviceProfile,
               geom: SweepGeometry, *, shared_idx: int,
               counts=None, timings=None, cycle_s: float = 1.0,
               ram_delta: float = 0.0, mode: str = "throughput",
               inner: str = "vmap",
               interpret: bool | None = None) -> SweepResult:
    """Evaluate C hardware configs against one packed profile pair.

    Returns per-config [C, L] hit rates, plus per-config predicted
    runtime seconds when ``counts`` (an `OpCounts`) and ``timings``
    (an `InCoreTimings`) are given — the full SDCM + ECM chain fused
    into one jitted dispatch per row shape.  Configs are grouped by
    their per-level A_MAX-bucket tuple and each group's C is padded to
    a power of two (chunked at `SWEEP_MAX_ELEMS`), so the compiled set
    stays bounded and every config's hit-rate bits are independent of
    which other configs share the sweep.
    """
    with telemetry.span("sdcm.sweep", n=geom.assoc.shape[0]):
        if inner not in ("vmap", "pallas"):
            raise ValueError(f"unknown sweep inner evaluator: {inner!r}")
        c, n_levels = geom.assoc.shape
        with_runtime = counts is not None
        if with_runtime and timings is None:
            raise ValueError("sweep_grid needs timings when counts are given")

        if with_runtime:
            from repro.core.incore import t_comp_cy, t_lsu_cy

            comp_cy = float(t_comp_cy(timings, counts, mode))
            lsu_cy = float(t_lsu_cy(timings, counts))
            mem_ops = float(counts.mem_ops)
        else:
            comp_cy = lsu_cy = mem_ops = 0.0

        rates = np.zeros((c, n_levels), dtype=np.float64)
        t_pred = np.zeros(c, dtype=np.float64) if with_runtime else None
        dispatches = 0
        compiles = 0

        if inner == "pallas":
            if interpret is None:
                interpret = interpret_mode()
            rates, dispatches, compiles = _pallas_rates(
                prd, crd, geom, shared_idx, interpret
            )
            if with_runtime:
                sig = ("sweep-chain", n_levels, shared_idx, mode, _pow2(c))
                compiles += _record_signature(sig)
                pad = _pow2(c) - c
                t = _chain_fn(n_levels, shared_idx, mode)(
                    jnp.asarray(
                        _pad_rows(rates.astype(np.float32), pad, 1.0)
                    ),
                    jnp.asarray(_pad_rows(geom.trans_beta, pad, 0.0)),
                    jnp.asarray(_pad_rows(geom.delta, pad, 0.0)),
                    jnp.asarray(_pad_rows(geom.cores, pad, 1.0)),
                    comp_cy, lsu_cy, mem_ops, ram_delta, cycle_s,
                )
                t_pred[:] = np.asarray(t, dtype=np.float64)[:c]
                dispatches += 1
            return SweepResult(rates, t_pred, dispatches, compiles)

        # group configs by their per-level bucket tuple (static per compile)
        groups: dict[tuple, list[int]] = {}
        for ci in range(c):
            groups.setdefault(
                _sweep_akey(geom.assoc[ci], geom.blocks[ci]), []
            ).append(ci)

        max_m = max(prd.m, crd.m)
        chunk_cap = max(1, _pow2(SWEEP_MAX_ELEMS // max_m) // 2)
        fn_args = (prd.d, prd.p, crd.d, crd.p)
        scalars = (comp_cy, lsu_cy, mem_ops, ram_delta, cycle_s)
        for a_key, idx_list in groups.items():
            fn = _sweep_fn(a_key, shared_idx, mode, with_runtime)
            for lo in range(0, len(idx_list), chunk_cap):
                idxs = np.asarray(idx_list[lo:lo + chunk_cap])
                n = len(idxs)
                with telemetry.span("sdcm.dispatch", n=n):
                    g = _pow2(n)
                    sig = ("sweep", a_key, shared_idx, mode, with_runtime,
                           g, prd.m, crd.m)
                    compiles += _record_signature(sig)
                    packed = _pack_sweep_inputs(geom, idxs, g, scalars)
                    with telemetry.span("sdcm.put", n=packed.nbytes):
                        packed = jax.device_put(packed)
                    with telemetry.span("sdcm.eval", n=g * max_m):
                        out = fn(*fn_args, packed)
                dispatches += 1
                with telemetry.span("sdcm.fetch", n=n):
                    if with_runtime:
                        r, t = out
                        t_pred[idxs] = np.asarray(t, dtype=np.float64)[:n]
                    else:
                        r = out
                    rates[idxs] = np.asarray(r, dtype=np.float64)[:n]

        if prd.total == 0:
            rates[:, :shared_idx] = 0.0
        if crd.total == 0:
            rates[:, shared_idx:] = 0.0
        return SweepResult(rates, t_pred, dispatches, compiles)
