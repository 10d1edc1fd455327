"""§3.3.1 table analog: reuse-profile computation throughput, plus the
`repro.api` grid amortization benchmark and the ISSUE-2 streaming
peak-memory benchmark.

The paper's speed contribution is replacing the O(N·M) stack method
with an O(N·log M) tree; this benchmark measures both on the same
traces (refs/s), plus the per-set variant the exact simulator uses.

The second half times the SAME 3-target x {1,2,4,8}-core prediction
grid two ways — the legacy per-call predictor loop (profiles recomputed
per cell, seed-quickstart style) vs one cached `Session` request — and
writes the speedup to ``BENCH_api_grid.json`` at the repo root.

The streaming benchmark drives ``reuse_distance_windows`` over a
synthetic :class:`SyntheticChunkSource` whose trace never exists in
memory, measuring peak RSS (each probe in its own subprocess, so
high-water marks don't bleed between runs; CPU backend only) and
throughput, and records
``BENCH_streaming.json`` at the repo root for the canonical >= 10M-ref
configuration (``--streaming-full``).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import warnings

import numpy as np

from benchmarks.common import REPO_ROOT, fmt_table, make_session, save_json
from repro.core.reuse.distance import (
    per_set_reuse_distances, reuse_distances, reuse_distances_ref,
)
from repro.core.trace.types import LabeledTrace, rebatch_windows


def synthetic_trace(n: int, working_set: int, seed: int = 0) -> np.ndarray:
    """Zipf-ish reuse: mixes hot lines with cold streaming."""
    rng = np.random.default_rng(seed)
    hot = rng.integers(0, working_set // 8, n // 2)
    cold = rng.integers(0, working_set, n - n // 2)
    mix = np.concatenate([hot, cold])
    rng.shuffle(mix)
    return (mix * 64 + 4096).astype(np.int64)


class SyntheticChunkSource:
    """ChunkedTraceSource whose windows are generated on demand.

    Zipf-ish reuse over a FIXED working set (``lines`` distinct cache
    lines, independent of ``n``): half the references hammer a hot
    eighth of the lines.  Each window is derived from ``(seed, window
    index)``, so no O(N) array ever exists — this is what lets the
    peak-RSS benchmark feed >= 10M references through the streaming scan
    inside a bounded-memory process.
    """

    def __init__(self, n: int, lines: int = 1 << 16, seed: int = 0):
        self.n = int(n)
        self.lines = int(lines)
        self.seed = int(seed)

    def __len__(self) -> int:
        return self.n

    _GEN_BLOCK = 1 << 14  # content is fixed per (seed, block index) —
    # the trace is identical for every requested window size

    def _blocks(self):
        for i, start in enumerate(range(0, self.n, self._GEN_BLOCK)):
            w = min(self._GEN_BLOCK, self.n - start)
            rng = np.random.default_rng((self.seed, i))
            hot = rng.integers(0, self.lines // 8, w // 2)
            cold = rng.integers(0, self.lines, w - w // 2)
            mix = np.concatenate([hot, cold])
            rng.shuffle(mix)
            yield mix * 64 + 4096

    def windows(self, window_size: int):
        pieces = (
            LabeledTrace(
                b, np.zeros(len(b), dtype=np.int32),
                np.zeros(len(b), dtype=bool),
            )
            for b in self._blocks()
        )
        yield from rebatch_windows(pieces, window_size)

    def materialize(self) -> np.ndarray:
        """Flat addresses (small-n equivalence/comparison probes only)."""
        return np.concatenate(list(self._blocks()))


_PROBE_CODE = r"""
import json, resource, sys, time
import numpy as np

kind, n, lines, window, seed, rate = sys.argv[1:7]
n, lines, window, seed = int(n), int(lines), int(window), int(seed)
rate = float(rate)

from benchmarks.reuse_throughput import SyntheticChunkSource
from repro.core.reuse.distance import (
    reuse_distance_windows, reuse_distances,
)
from repro.core.reuse.profile import (
    profile_from_distances, profile_from_distances_incremental,
)
from repro.core.reuse.sampled import (
    sampled_profile_windows, sampled_reuse_profile,
)

src = SyntheticChunkSource(n, lines, seed)
t0 = time.perf_counter()
if kind == "baseline":
    # import-only RSS floor (plus one tiny scan so the XLA arena and
    # jit machinery are warm, comparable with the real probes)
    prof = profile_from_distances_incremental(
        reuse_distance_windows(
            SyntheticChunkSource(min(n, 4096), lines, seed),
            64, window_size=window,
        )
    )
elif kind == "streaming":
    prof = profile_from_distances_incremental(
        reuse_distance_windows(src, 64, window_size=window)
    )
elif kind == "sampled":
    # SHARDS path: windows are hash-filtered before the streaming scan,
    # so state tracks only the sampled slice of the working set
    prof = sampled_profile_windows(src, 64, rate=rate,
                                   window_size=window)
elif kind == "sampled_mem":
    prof = sampled_reuse_profile(src.materialize(), 64, rate=rate)
else:  # in-memory path: materialize + reuse_distances (auto engine —
    # the offline vectorized pass at these sizes since ISSUE-5); the
    # profile-equality assertion below doubles as a cross-engine check
    prof = profile_from_distances(
        reuse_distances(src.materialize(), 64)
    )
dt = time.perf_counter() - t0
peak_kib = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
print(json.dumps({
    "kind": kind, "n": n, "lines": lines, "window": window,
    "seconds": dt, "refs_per_s": n / dt,
    "peak_rss_mib": peak_kib / 1024.0,
    "profile_total": int(prof.total),
    "profile_distinct_distances": int(len(prof.distances)),
    "error_bound": prof.error_bound,
}))
"""


def _rss_probe(kind: str, n: int, *, lines: int, window: int = 0,
               seed: int = 0, rate: float = 1.0) -> dict:
    """Run one scan in a fresh subprocess; return its self-reported
    stats (ru_maxrss is a per-process high-water mark).

    Only where no backend but the CPU is used: on a chip host the child
    would fight this process (or the previous probe) for the device.
    """
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        raise RuntimeError(
            "peak-RSS probes start one JAX child per probe and run only "
            "with JAX_PLATFORMS=cpu; never start a JAX child from a "
            "process that may hold the chip"
        )
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        str(REPO_ROOT / "src") + os.pathsep + str(REPO_ROOT)
        + (os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    )
    out = subprocess.run(
        [sys.executable, "-c", _PROBE_CODE,
         kind, str(n), str(lines), str(window), str(seed), str(rate)],
        capture_output=True, text=True, env=env, cwd=REPO_ROOT, check=True,
    )
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    print(f"  {kind:11s} n={n:>11,} window={window:>8,}: "
          f"{rec['refs_per_s']:>10,.0f} refs/s, "
          f"peak RSS {rec['peak_rss_mib']:.0f} MiB")
    return rec


def streaming_benchmark(full: bool = False) -> dict:
    """Peak-RSS + throughput: streaming vs in-memory reuse scans.

    ``full`` runs the ISSUE-2 acceptance configuration (>= 10M refs);
    the default is the CI smoke size.  The acceptance evidence is the
    ``rss_growth`` ratio: multiplying the trace length by
    ``large_n/small_n`` must leave streaming peak RSS ~flat, because the
    scan state is bounded by O(window + working set), never O(N).
    """
    if full:
        small_n, large_n = 1_000_000, 10_000_000
        lines, windows, compare_n = 1 << 16, (8_192, 16_384), 200_000
    else:
        small_n, large_n = 60_000, 240_000
        lines, windows, compare_n = 1 << 13, (8_192,), 60_000

    baseline = _rss_probe("baseline", small_n, lines=lines,
                          window=windows[0])
    streaming_rows = []
    for window in windows:
        rec_small = _rss_probe("streaming", small_n, lines=lines,
                               window=window)
        rec_large = _rss_probe("streaming", large_n, lines=lines,
                               window=window)
        streaming_rows.append({
            "window": window,
            "small": rec_small,
            "large": rec_large,
            "rss_growth": rec_large["peak_rss_mib"]
            / max(rec_small["peak_rss_mib"], 1e-9),
            # scan-state RSS with the import/XLA floor removed
            "small_delta_mib": rec_small["peak_rss_mib"]
            - baseline["peak_rss_mib"],
            "large_delta_mib": rec_large["peak_rss_mib"]
            - baseline["peak_rss_mib"],
            "throughput_ratio": rec_large["refs_per_s"]
            / max(rec_small["refs_per_s"], 1e-9),
        })
    inmem = _rss_probe("inmemory", compare_n, lines=lines)
    stream_cmp = _rss_probe("streaming", compare_n, lines=lines,
                            window=windows[0])
    # same trace -> identical profiles, or the scans disagree
    for key in ("profile_total", "profile_distinct_distances"):
        assert inmem[key] == stream_cmp[key], (key, inmem, stream_cmp)

    payload = {
        "config": {
            "full": full, "small_n": small_n, "large_n": large_n,
            "working_set_lines": lines, "windows": list(windows),
            "compare_n": compare_n,
            "trace_bytes_if_materialized": large_n * 8,
        },
        "baseline": baseline,
        "streaming": streaming_rows,
        "inmemory_compare": inmem,
        "streaming_compare": stream_cmp,
        "speedup_vs_inmemory_at_compare_n":
            stream_cmp["refs_per_s"] / inmem["refs_per_s"],
    }
    growth = max(r["rss_growth"] for r in streaming_rows)
    scale = large_n / small_n
    print(f"  -> peak-RSS growth {growth:.2f}x for a {scale:.0f}x longer "
          f"trace (streaming state is O(window + working set)); "
          f"streaming runs at "
          f"{payload['speedup_vs_inmemory_at_compare_n']:.2f}x the "
          f"in-memory (offline-engine) pass at n={compare_n:,} — it "
          f"trades throughput for bounded memory")
    # regression gates (the CI smoke job runs these at small sizes):
    # 1. throughput must stay ~flat in n — an O(N)-per-step fallback to
    #    the monolithic scan tanks the large/small ratio (measured:
    #    in-memory drops ~4x from 60k to 200k refs, streaming doesn't)
    for row in streaming_rows:
        assert row["throughput_ratio"] > 0.5, row
    # 2. the baseline-subtracted high-water mark must not grow with the
    #    trace length (generous slack: RSS deltas are noisy at MiB
    #    scale next to the ~400 MiB import/XLA floor)
    for row in streaming_rows:
        assert row["large_delta_mib"] < row["small_delta_mib"] + 96, row
    assert growth < 1.5, payload
    if full:
        (REPO_ROOT / "BENCH_streaming.json").write_text(
            json.dumps(payload, indent=2)
        )
    save_json("streaming" + ("_full" if full else "_smoke"), payload)
    return payload


def sampling_benchmark(full: bool = False) -> dict:
    """Peak-RSS gate for the SHARDS-sampled profile path (ISSUE-9).

    The sampled windowed pass hash-filters every address window before
    the streaming scan, so its state is O(window + rate * working set)
    — growing the trace past 1M references must leave peak RSS ~flat.
    Each probe runs in its own subprocess (``ru_maxrss`` high-water
    marks don't bleed), mirroring the streaming benchmark; the smoke
    gate (CI ``sampling-gate`` job) runs the >= 1M-ref point, ``full``
    the 10M one and records ``BENCH_sampling.json``.
    """
    if full:
        small_n, large_n = 1_000_000, 10_000_000
        lines, window, compare_n = 1 << 16, 8_192, 200_000
    else:
        small_n, large_n = 250_000, 1_000_000
        lines, window, compare_n = 1 << 13, 8_192, 60_000
    rate = 0.25

    baseline = _rss_probe("baseline", small_n, lines=lines, window=window)
    rec_small = _rss_probe("sampled", small_n, lines=lines, window=window,
                           rate=rate)
    rec_large = _rss_probe("sampled", large_n, lines=lines, window=window,
                           rate=rate)
    # the windowed sampled pass must agree with the in-memory sampled
    # pass on the same trace (bit-identity is property-tested; this is
    # the cross-subprocess end-to-end check, error bound included)
    win_cmp = _rss_probe("sampled", compare_n, lines=lines, window=window,
                         rate=rate)
    mem_cmp = _rss_probe("sampled_mem", compare_n, lines=lines, rate=rate)
    for key in ("profile_total", "profile_distinct_distances",
                "error_bound"):
        assert win_cmp[key] == mem_cmp[key], (key, win_cmp, mem_cmp)

    payload = {
        "config": {
            "full": full, "small_n": small_n, "large_n": large_n,
            "rate": rate, "working_set_lines": lines, "window": window,
            "compare_n": compare_n,
        },
        "baseline": baseline,
        "small": rec_small,
        "large": rec_large,
        "rss_growth": rec_large["peak_rss_mib"]
        / max(rec_small["peak_rss_mib"], 1e-9),
        "small_delta_mib": rec_small["peak_rss_mib"]
        - baseline["peak_rss_mib"],
        "large_delta_mib": rec_large["peak_rss_mib"]
        - baseline["peak_rss_mib"],
        "declared_error_bound": rec_large["error_bound"],
        "windowed_vs_inmemory_identical": True,
    }
    scale = large_n / small_n
    print(f"  -> peak-RSS growth {payload['rss_growth']:.2f}x for a "
          f"{scale:.0f}x longer trace at rate {rate} (sampled state is "
          f"O(window + rate * working set)); declared error bound "
          f"{rec_large['error_bound']:.4f} at n={large_n:,}")
    # gates: flat high-water mark in n (same slack policy as the
    # streaming gate — RSS deltas are noisy next to the XLA floor),
    # and a nontrivial declared bound.  The bound need not shrink with
    # n here: the working set is FIXED, so line masses grow with n and
    # the cluster variance stays ~constant (only the uniform-trace
    # bound is monotone in n).
    assert payload["rss_growth"] < 1.5, payload
    assert payload["large_delta_mib"] < payload["small_delta_mib"] + 96, \
        payload
    assert 0.0 < rec_large["error_bound"] < 1.0, payload
    if full:
        (REPO_ROOT / "BENCH_sampling.json").write_text(
            json.dumps(payload, indent=2)
        )
    save_json("sampling" + ("_full" if full else "_smoke"), payload)
    return payload


# ---------------------------------------------------------------------------
# Profile-build benchmark (ISSUE-5): batched-fused vs sequential host path.
# ---------------------------------------------------------------------------


def _profile_case_per_set(n: int, num_sets: int, lines: int) -> dict:
    """Per-set distance pass: batched engine vs sequential streaming scan.

    The sequential host path is the pre-batching production pipeline —
    ONE chunked Fenwick scan over the stably-concatenated per-set
    subtraces (bit-identical to the monolithic scan, and the only
    sequential engine that stays feasible at 1M refs).
    """
    from repro.core.reuse.distance import (
        per_set_reuse_distances, reuse_distances_streaming, split_by_set,
    )

    addrs = SyntheticChunkSource(n, lines).materialize()
    segments, order = split_by_set(addrs, line_size=64, num_sets=num_sets)
    concat = np.concatenate(segments)

    t0 = time.perf_counter()
    rd_seq_sorted = reuse_distances_streaming(concat)
    t_seq = time.perf_counter() - t0
    rd_seq = np.empty_like(rd_seq_sorted)
    rd_seq[order] = rd_seq_sorted

    # first batched run pays the per-shape-bucket XLA compiles (cached
    # for the life of the process, like every other jit in the repo);
    # the gate measures steady state and reports the cold time alongside
    t0 = time.perf_counter()
    rd_bat = per_set_reuse_distances(addrs, line_size=64,
                                     num_sets=num_sets, method="batched")
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    rd_bat = per_set_reuse_distances(addrs, line_size=64,
                                     num_sets=num_sets, method="batched")
    t_bat = time.perf_counter() - t0

    assert np.array_equal(rd_seq, rd_bat), "per-set batched != sequential"
    return {
        "shape": "per_set", "n": n, "num_sets": num_sets,
        "working_set_lines": lines,
        "sequential_s": t_seq, "batched_s": t_bat, "batched_cold_s": t_cold,
        "sequential_refs_per_s": n / t_seq, "batched_refs_per_s": n / t_bat,
        "speedup": t_seq / max(t_bat, 1e-12), "bit_identical": True,
    }


def _profile_case_multicore(n: int, cores: int, lines: int) -> dict:
    """Per-core profile builds: batched + fused histogram vs the
    sequential streaming scan + host np.unique accumulation."""
    import jax.numpy as jnp

    from repro.core.reuse.batched import reuse_distances_batched
    from repro.core.reuse.distance import reuse_distance_windows
    from repro.core.reuse.fused import (
        FusedReuseHistogram, profile_from_binned_hist,
    )
    from repro.core.reuse.profile import (
        profile_from_distances, profile_from_distances_incremental,
    )
    from repro.kernels.reuse_hist import reuse_hist_ref

    per_core = n // cores
    segments = [
        SyntheticChunkSource(per_core, lines, seed=c).materialize() // 64
        for c in range(cores)
    ]

    t0 = time.perf_counter()
    seq_profiles = [
        profile_from_distances_incremental(reuse_distance_windows(s))
        for s in segments
    ]
    t_seq = time.perf_counter() - t0

    def batched_build():
        rds = reuse_distances_batched(segments)
        accs = []
        for rd in rds:
            acc = FusedReuseHistogram()
            acc.update(jnp.asarray(rd))
            accs.append(acc)
        profiles = [a.profile() for a in accs]
        return rds, accs, profiles

    t0 = time.perf_counter()
    batched_build()  # pays the histogram-kernel compiles once
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    rds, accs, binned_profiles = batched_build()
    t_bat = time.perf_counter() - t0

    # identity: exact distances reproduce the sequential profiles bit
    # for bit; the fused histograms equal the reference binning of the
    # exact distances (counts exactly, distance mass to f32 tolerance)
    for rd, sp, acc in zip(rds, seq_profiles, accs):
        p = profile_from_distances(rd)
        assert np.array_equal(p.distances, sp.distances)
        assert np.array_equal(p.counts, sp.counts)
        ref = np.asarray(reuse_hist_ref(
            jnp.asarray(rd.astype(np.float32)),
            jnp.ones((len(rd),), jnp.float32),
        ))
        hist = acc.histogram()
        assert np.array_equal(hist[0], ref), "fused counts != ref binning"
    del binned_profiles
    return {
        "shape": "multi_core", "n": n, "cores": cores,
        "working_set_lines": lines,
        "sequential_s": t_seq, "batched_s": t_bat, "batched_cold_s": t_cold,
        "sequential_refs_per_s": n / t_seq, "batched_refs_per_s": n / t_bat,
        "speedup": t_seq / max(t_bat, 1e-12), "bit_identical": True,
    }


def profile_build_benchmark(full: bool = True) -> dict:
    """Batched-fused profile pipeline vs the sequential host path.

    Two shapes per size: the per-set decomposition (one segment per
    cache set — exact-LRU's workload; wide buckets routed to the
    vmapped Fenwick engine) and per-core profile builds (few long
    segments routed to the offline engine, fused into the Pallas
    histogram).  The CI gate (``--profile-gate``) asserts bit-/
    tolerance-identity and >= 3x speedup for both shapes at the 1M
    point; ``BENCH_profile.json`` records the canonical run.
    """
    sizes = (100_000, 1_000_000) if full else (60_000,)
    rows = []
    for n in sizes:
        per_set = _profile_case_per_set(n, num_sets=512, lines=1 << 16)
        multi = _profile_case_multicore(n, cores=8, lines=1 << 13)
        rows.extend([per_set, multi])
        for r in (per_set, multi):
            print(f"  {r['shape']:10s} n={n:>10,}: "
                  f"seq {r['sequential_refs_per_s']:>10,.0f} refs/s, "
                  f"batched {r['batched_refs_per_s']:>10,.0f} refs/s "
                  f"-> {r['speedup']:.1f}x")
    payload = {
        "config": {"full": full, "sizes": list(sizes), "gate_n": 1_000_000,
                   "gate_speedup": 3.0},
        "cases": rows,
    }
    gate_rows = [r for r in rows if r["n"] == 1_000_000]
    for r in gate_rows:
        assert r["bit_identical"], r
        assert r["speedup"] >= 3.0, (
            f"profile-build gate: {r['shape']} at 1M is only "
            f"{r['speedup']:.2f}x the sequential host path", r,
        )
    if full:
        (REPO_ROOT / "BENCH_profile.json").write_text(
            json.dumps(payload, indent=2)
        )
    save_json("profile_build" + ("" if full else "_smoke"), payload)
    return payload


CANONICAL_CORES = (1, 2, 4, 8)  # the acceptance grid (3 targets x these)


def api_grid_benchmark(n: int = 64, core_counts=CANONICAL_CORES) -> dict:
    """Legacy per-call loop vs one cached Session request on an
    identical 3-CPU-target grid (the ISSUE-1 acceptance number).

    The repo-root ``BENCH_api_grid.json`` is only (re)written for the
    canonical 3-target x {1,2,4,8} grid — smoke runs with toy grids
    must not clobber the recorded baseline.  Every run also lands in
    experiments/results/ via save_json.
    """
    import json

    from repro.api import PredictionRequest
    from repro.core.predictor import PPTMulticorePredictor
    from repro.hw.targets import CPU_TARGETS
    from repro.workloads.polybench import make_atax

    workload = make_atax(n=n)
    trace = workload.trace()

    # legacy: one predictor per target, one predict() per cell — every
    # call re-derives mimicked traces + reuse profiles from scratch
    t0 = time.perf_counter()
    legacy_cells = 0
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for target in CPU_TARGETS.values():
            predictor = PPTMulticorePredictor(target)
            for cores in core_counts:
                predictor.predict(trace, cores, workload.op_counts)
                legacy_cells += 1
    t_legacy = time.perf_counter() - t0

    # new API: one declarative request, artifacts computed once
    request = PredictionRequest(
        targets=tuple(CPU_TARGETS),
        core_counts=tuple(core_counts),
        counts=workload.op_counts,
    )
    # cold run on a throwaway session pays the one-time XLA compile of
    # the batched SDCM kernel; the timed run measures steady state
    # (the legacy numpy loop has no compile cost to exclude)
    t0 = time.perf_counter()
    make_session().predict(trace, request)
    t_cold = time.perf_counter() - t0
    session = make_session()
    t0 = time.perf_counter()
    result = session.predict(trace, request)
    t_session = time.perf_counter() - t0

    assert len(result) == legacy_cells, (len(result), legacy_cells)
    payload = {
        "grid": {
            "targets": list(CPU_TARGETS),
            "core_counts": list(core_counts),
            "cells": legacy_cells,
            "workload": workload.name,
            "trace_refs": len(trace),
        },
        "legacy_s": t_legacy,
        "session_s": t_session,
        "session_cold_s": t_cold,
        "speedup": t_legacy / max(t_session, 1e-12),
        "profile_builds": session.stats.profile_builds,
        "profile_cache_hits": session.stats.profile_hits,
    }
    if tuple(core_counts) == CANONICAL_CORES:
        (REPO_ROOT / "BENCH_api_grid.json").write_text(
            json.dumps(payload, indent=2)
        )
    save_json("BENCH_api_grid", payload)
    print(f"\napi grid ({legacy_cells} cells): legacy loop {t_legacy:.2f}s, "
          f"Session {t_session:.2f}s -> {payload['speedup']:.1f}x "
          f"({session.stats.profile_builds} profile builds, "
          f"{session.stats.profile_hits} cache hits)")
    return payload


def run(quick: bool = True) -> dict:
    sizes = [20_000, 60_000] if quick else [20_000, 60_000, 200_000]
    rows, records = [], []
    for n in sizes:
        tr = synthetic_trace(n, working_set=n // 4)
        t0 = time.perf_counter()
        rd_tree = reuse_distances(tr, 64)
        t_tree = time.perf_counter() - t0

        t_stack = None
        if n <= 60_000:
            t0 = time.perf_counter()
            rd_stack = reuse_distances_ref((tr // 64))
            t_stack = time.perf_counter() - t0
            assert np.array_equal(rd_tree, rd_stack), "tree != stack oracle"

        t0 = time.perf_counter()
        per_set_reuse_distances(tr, line_size=64, num_sets=64)
        t_set = time.perf_counter() - t0

        rows.append([
            n,
            f"{n / t_tree:,.0f}",
            f"{n / t_stack:,.0f}" if t_stack else "-",
            f"{n / t_set:,.0f}",
            f"{t_stack / t_tree:.1f}x" if t_stack else "-",
        ])
        records.append({
            "n": n, "tree_refs_per_s": n / t_tree,
            "stack_refs_per_s": (n / t_stack) if t_stack else None,
            "per_set_refs_per_s": n / t_set,
        })
    print(fmt_table(
        ["refs", "tree refs/s", "stack refs/s", "per-set refs/s",
         "tree speedup"], rows))
    grid = api_grid_benchmark(n=48 if quick else 96)
    print("\nprofile builds (batched-fused vs sequential host path):")
    profile = profile_build_benchmark(full=not quick)
    print("\nstreaming scans (peak RSS per subprocess):")
    streaming = streaming_benchmark(full=not quick)
    summary = {"records": records, "api_grid": grid,
               "profile_build": profile, "streaming": streaming}
    save_json("reuse_throughput" + ("_quick" if quick else ""), summary)
    return summary


if __name__ == "__main__":
    if "--streaming-smoke" in sys.argv:
        streaming_benchmark(full=False)
    elif "--streaming-full" in sys.argv:
        streaming_benchmark(full=True)
    elif "--sampling-smoke" in sys.argv:
        # CI gate: >= 1M-ref sampled profile at ~flat peak RSS
        sampling_benchmark(full=False)
    elif "--sampling-full" in sys.argv:
        sampling_benchmark(full=True)
    elif "--profile-gate" in sys.argv:
        # CI gate: identity + >= 3x at the 1M point (both shapes)
        profile_build_benchmark(full=True)
    elif "--profile-smoke" in sys.argv:
        profile_build_benchmark(full=False)
    else:
        run(quick="--full" not in sys.argv)
