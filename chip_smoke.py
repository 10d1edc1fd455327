"""Bring-up run of the predictor's main path on one TPU chip.

    python chip_smoke.py

One process, one chip, the normal entry points.  The phases run in
order; each prints its wall time and what it checked, and any failed
check ends the run with a non-zero exit and no result line:

* device    -- the default backend is a TPU and Pallas kernels compile
               natively (no interpret mode).
* profiles  -- ``polybench/atx`` at ``validation-xxl`` (1,082,640
               references, 271,960 distinct addresses) through
               ``Session`` for 1/2/4/8 cores: the exact host path and
               the device path (binned: streaming Fenwick windows into
               the ``reuse_hist`` kernel); binned hit rates stay within
               ``BINNED_TOLERANCE`` of exact ones, and the kernel's
               distance mass matches a float64 host histogram.
* exact_lru -- ``Session.ground_truth_hit_rates`` on the same trace;
               per-set distances from the device Fenwick engine are
               bit-identical to the host offline engine.
* service   -- ``PredictionServer`` over ``PredictionService`` on an
               ephemeral port; concurrent ``/predict`` answers are
               bit-identical to sequential ``Session.predict``, and the
               batched f32 grid stays within the float64 oracle's
               tolerance.
* sweep     -- a 10,000-config ``sweep_grid`` from a ``repro.explore``
               search space, vmap and Pallas inner evaluators; vmap
               rates are bit-identical to ``batched_hit_rates``, and
               both evaluators stay within the float64 oracle's
               tolerance.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
The compile cache directory and its entry count are printed at the
start and at the end.  Tests run every phase at the ``smoke`` size on
the CPU (``tests/test_chip_smoke.py``).
"""
from __future__ import annotations

import dataclasses
import json
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

WORKLOAD = "polybench/atx"
MODEL_WORKLOAD = "model/llama3_8b/decode"
CORES = (1, 2, 4, 8)
WINDOW = 1 << 14               # device-path streaming window
LRU_TARGET = "i7-5960X"
SERVICE_TARGETS = ("i7-5960X", "Xeon E5-2699 v4", "EPYC 7702P", "tpu-v5e")
# an f32 hit rate against the float64 oracle: the tolerance of
# tests/api/test_batched_sdcm.py
GRID_ORACLE_TOL = 1e-6
# the Pallas and vmap inner evaluators against each other: the
# tolerance of tests/explore/test_explore_sweep.py
PALLAS_VMAP_TOL = 1e-6
# the histogram kernel's f32 distance mass against a float64 host sum
# of the same window: f32 rounding reads ~1e-7, bf16 operands ~1e-3
HIST_MASS_RTOL = 1e-5


@dataclasses.dataclass(frozen=True)
class Size:
    """How big each phase runs: the workload preset and sweep axes."""

    preset: str
    sets: tuple[int, ...]
    ways: tuple[int, ...]
    latency_cy: tuple[float, ...]
    beta_cy: tuple[float, ...]
    clients: int


SIZES = {
    # 5 sets x 4 ways x 25 latencies x 5 betas x 4 core counts = 10,000
    "full": Size(
        preset="validation-xxl",
        sets=(1024, 2048, 4096, 8192, 16384),
        ways=(4, 8, 16, 20),
        latency_cy=tuple(float(x) for x in range(20, 70, 2)),
        beta_cy=(4.0, 6.0, 8.0, 10.0, 12.0),
        clients=6,
    ),
    "smoke": Size(
        preset="smoke",
        sets=(1024, 4096),
        ways=(8, 20),
        latency_cy=(36.0, 60.0),
        beta_cy=(8.0,),
        clients=3,
    ),
}


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def report(phase: str, seconds: float, checked: str) -> None:
    print(f"[{phase}] {seconds:.3f}s  {checked}", flush=True)


def check_device() -> dict:
    """The default backend is a TPU, and kernels compile natively."""
    from repro.kernels import interpret_mode

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke.py needs a TPU; JAX found {dev.platform!r}"
        )
    check(interpret_mode() is False, "Pallas kernels compile natively")
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    print(f"[device] {info['kind']} x{info['count']}, Pallas native",
          flush=True)
    return info


def _rates_by_cell(predset) -> dict:
    return {(p.target, p.cores, p.strategy, p.mode): p.hit_rates
            for p in predset}


def phase_profiles(size: Size):
    """Exact host profiles vs device-binned profiles for every core
    count; returns the workload and the exact (batched-SDCM) Session
    the later phases reuse."""
    from repro.api import AnalyticalSDCM, PredictionRequest, Session
    from repro.core.reuse.distance import reuse_distances
    from repro.core.reuse.fused import BINNED_TOLERANCE
    from repro.hw.targets import CPU_TARGETS
    from repro.kernels import interpret_mode
    from repro.kernels.reuse_hist import reuse_histogram_moments
    from repro.kernels.reuse_hist.reuse_hist import NUM_BINS, _bin_ids
    from repro.workloads import registry

    w = registry.resolve(WORKLOAD, size.preset)
    request = PredictionRequest(targets=tuple(CPU_TARGETS),
                                core_counts=CORES)
    exact = Session(cache_model=AnalyticalSDCM(backend="batched"))
    t0 = time.perf_counter()
    exact_rates = _rates_by_cell(exact.predict(w, request))
    t_exact = time.perf_counter() - t0
    refs = len(exact.load(w)[1])

    binned = Session(cache_model=AnalyticalSDCM(backend="batched"),
                     binned=True, window_size=WINDOW)
    t0 = time.perf_counter()
    binned_rates = _rates_by_cell(binned.predict(w, request))
    t_binned = time.perf_counter() - t0
    check(binned.stats.streaming_builds == len(CORES),
          "every binned cell streamed through the device windows")
    gap = max(abs(binned_rates[k][lvl] - v)
              for k, rates in exact_rates.items()
              for lvl, v in rates.items())
    check(gap <= BINNED_TOLERANCE,
          f"binned within {BINNED_TOLERANCE} of exact (gap {gap:.3e})")

    # the kernel's f32 distance mass on one real window vs float64
    trace = exact.load(w)[1]
    rd = reuse_distances(trace.addresses[:WINDOW], 64)
    d = jnp.asarray(rd.astype(np.float32))
    hist = np.asarray(reuse_histogram_moments(d, interpret=interpret_mode()),
                      dtype=np.float64)
    bins = np.asarray(_bin_ids(d))
    want = np.zeros((2, NUM_BINS))
    np.add.at(want[0], bins, 1.0)
    np.add.at(want[1], bins, np.maximum(rd, 0).astype(np.float64))
    check(np.array_equal(hist[0], want[0]), "kernel counts exact")
    nz = want[1] > 0
    mass_gap = float(np.max(np.abs(hist[1][nz] - want[1][nz]) / want[1][nz]))
    check(mass_gap <= HIST_MASS_RTOL,
          f"kernel distance mass within {HIST_MASS_RTOL} of float64 "
          f"(gap {mass_gap:.3e})")
    report("profiles", t_exact + t_binned,
           f"{WORKLOAD}@{size.preset} {refs} refs, cores {list(CORES)}: "
           f"exact host {t_exact:.3f}s, binned device {t_binned:.3f}s; "
           f"max |binned-exact| {gap:.3e} <= {BINNED_TOLERANCE}; "
           f"kernel mass rel gap {mass_gap:.3e} <= {HIST_MASS_RTOL}")
    return w, exact


def _lru_walk(addresses: np.ndarray, levels, engine: str):
    """``simulate_hierarchy`` with an explicit per-set engine: per-level
    cumulative hit rates and per-set distances."""
    from repro.core.reuse.batched import reuse_distances_batched
    from repro.core.reuse.distance import split_by_set

    total = len(addresses)
    current = addresses
    rates, dists = {}, []
    for cfg in levels:
        segs, order = split_by_set(current, line_size=cfg.line_size,
                                   num_sets=cfg.num_sets)
        rds = np.empty(len(current), dtype=np.int64)
        rds[order] = np.concatenate(reuse_distances_batched(segs,
                                                            engine=engine))
        hit = (rds >= 0) & (rds < cfg.effective_assoc)
        rates[cfg.name] = 1.0 - (len(current) - int(hit.sum())) / total
        dists.append(rds)
        current = current[~hit]
    return rates, dists


def phase_exact_lru(w, session) -> None:
    """Ground-truth LRU on the device engine vs the host engine."""
    from repro.hw.targets import resolve_target

    target = resolve_target(LRU_TARGET)
    t0 = time.perf_counter()
    truth = session.ground_truth_hit_rates(w, target, 1)
    t_truth = time.perf_counter() - t0
    addresses = session.load(w)[1].addresses
    t0 = time.perf_counter()
    dev_rates, dev_dists = _lru_walk(addresses, target.levels, "fenwick")
    t_dev = time.perf_counter() - t0
    t0 = time.perf_counter()
    host_rates, host_dists = _lru_walk(addresses, target.levels, "offline")
    t_host = time.perf_counter() - t0
    for cfg, a, b in zip(target.levels, dev_dists, host_dists):
        check(np.array_equal(a, b),
              f"{cfg.name} per-set distances: fenwick == offline")
    check(dev_rates == host_rates == truth,
          "ground-truth rates == both engines' rates")
    report("exact_lru", t_truth + t_dev + t_host,
           f"{LRU_TARGET} x1 core: ground truth {t_truth:.3f}s, "
           f"device fenwick {t_dev:.3f}s, host offline {t_host:.3f}s; "
           f"per-set distances bit-identical on "
           f"{[cfg.name for cfg in target.levels]}; rates {truth}")


def phase_service(size: Size, session) -> None:
    """Concurrent HTTP /predict vs sequential Session.predict, plus the
    batched grid's gap to the float64 oracle."""
    from repro.api import AnalyticalSDCM
    from repro.service.client import ServiceClient
    from repro.service.server import PredictionServer, build_request
    from repro.service.service import PredictionService

    payloads = (
        {"workload": WORKLOAD, "sizes": size.preset,
         "targets": list(SERVICE_TARGETS), "core_counts": list(CORES)},
        {"workload": MODEL_WORKLOAD, "sizes": "smoke",
         "targets": ["tpu-v5e"], "core_counts": [1]},
        # the same cells through the legacy alias: dedup must coalesce
        {"workload": "atx", "sizes": size.preset,
         "targets": list(SERVICE_TARGETS), "core_counts": list(CORES)},
    )
    failures: list[str] = []
    oracle = AnalyticalSDCM(backend="numpy")
    t0 = time.perf_counter()
    with PredictionService(session=session) as service:
        server = PredictionServer(service, "127.0.0.1", 0)
        expected, oracle_gap = [], 0.0
        for payload in payloads:
            source = server.resolver.get(payload["workload"],
                                         payload["sizes"])
            request = build_request(payload, source)
            result = session.predict(source, request)
            expected.append(json.loads(result.to_json())["predictions"])
            for cell, pred in zip(request.cells(), result):
                art = session.artifacts(
                    source, cell.cores, strategy=cell.strategy,
                    line_size=cell.target.levels[0].line_size,
                )
                want = oracle.hit_rates(cell.target, art)
                oracle_gap = max(oracle_gap, *(abs(pred.hit_rates[k] - v)
                                               for k, v in want.items()))
        t_ref = time.perf_counter() - t0

        def run_client(client: ServiceClient) -> None:
            for payload, want in zip(payloads, expected):
                try:
                    got = client.predict(**payload)["predictions"]
                except Exception as exc:  # noqa: BLE001 — collected
                    failures.append(f"{payload['workload']}: {exc}")
                    continue
                if got != want:
                    failures.append(f"{payload['workload']}: response "
                                    "diverged from sequential predict")

        server.serve_background()
        t1 = time.perf_counter()
        try:
            client = ServiceClient(server.url, timeout=600.0)
            client.wait_ready()
            threads = [threading.Thread(target=run_client, args=(client,))
                       for _ in range(size.clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            stats = service.snapshot()["service"]
        finally:
            server.shutdown()
            server.server_close()
        t_serve = time.perf_counter() - t1
    check(not failures, f"service responses bit-identical ({failures})")
    check(oracle_gap <= GRID_ORACLE_TOL,
          f"batched grid within {GRID_ORACLE_TOL} of the float64 oracle "
          f"(gap {oracle_gap:.3e})")
    report("service", t_ref + t_serve,
           f"{size.clients} clients x {len(payloads)} payloads in "
           f"{stats['batches']} batches ({stats['deduped']} deduped): "
           f"sequential reference {t_ref:.3f}s, served {t_serve:.3f}s; "
           f"responses bit-identical; max |grid-oracle| "
           f"{oracle_gap:.3e} <= {GRID_ORACLE_TOL}")


def phase_sweep(size: Size, w, session) -> None:
    """Fused config sweep, vmap and Pallas inner evaluators."""
    from repro.api.batched import batched_hit_rates
    from repro.api.stages import shared_level_index
    from repro.core import sdcm
    from repro.explore import SearchSpace
    from repro.explore.engine import FusedSweepEvaluator
    from repro.hw.targets import resolve_target

    space = SearchSpace(target=LRU_TARGET, level="L3", sets=size.sets,
                        ways=size.ways, latency_cy=size.latency_cy,
                        beta_cy=size.beta_cy, cores=CORES)
    configs = space.configs()
    rates, seconds = {}, {}
    for inner in ("vmap", "pallas"):
        ev = FusedSweepEvaluator(w, space, session=session, inner=inner)
        t0 = time.perf_counter()
        res = ev.evaluate(configs)
        seconds[inner] = time.perf_counter() - t0
        check(np.all(np.isfinite(res.scores)), f"{inner}: finite scores")
        rates[inner] = res.rates

    base = resolve_target(space.target)
    li, shared = space.level_index(base), shared_level_index(base)
    t0 = time.perf_counter()
    items = [(cfg.apply(base, li),
              session.artifacts(w, cfg.cores, strategy=cfg.strategy,
                                line_size=cfg.line_size))
             for cfg in configs]
    ref = np.array([[r[lvl.name] for lvl in base.levels]
                    for r in batched_hit_rates(items)])
    t_ref = time.perf_counter() - t0
    check(np.array_equal(rates["vmap"], ref),
          "vmap sweep rates == batched_hit_rates, bit for bit")

    # float64 oracle, once per distinct (profile, geometry) row
    oracle_rows: dict[tuple, float] = {}
    oracle = np.empty_like(ref)
    for i, (target, art) in enumerate(items):
        for lv, lvl in enumerate(target.levels):
            prof = art.crd if lv >= shared else art.prd
            key = (id(prof), lvl.effective_assoc, lvl.num_lines)
            if key not in oracle_rows:
                oracle_rows[key] = sdcm.hit_rate(
                    prof, lvl.effective_assoc, lvl.num_lines
                )
            oracle[i, lv] = oracle_rows[key]
    gaps = {k: float(np.max(np.abs(v - oracle))) for k, v in rates.items()}
    for inner, g in gaps.items():
        check(g <= GRID_ORACLE_TOL, f"{inner} within {GRID_ORACLE_TOL} of "
              f"the float64 oracle (gap {g:.3e})")
    gap = float(np.max(np.abs(rates["pallas"] - rates["vmap"])))
    check(gap <= PALLAS_VMAP_TOL,
          f"pallas within {PALLAS_VMAP_TOL} of vmap (gap {gap:.3e})")
    report("sweep", sum(seconds.values()) + t_ref,
           f"{len(configs)} configs x {len(base.levels)} levels: vmap "
           f"{seconds['vmap']:.3f}s, pallas {seconds['pallas']:.3f}s, "
           f"batched_hit_rates {t_ref:.3f}s; vmap bit-identical; max "
           f"|vmap-oracle| {gaps['vmap']:.3e}, |pallas-oracle| "
           f"{gaps['pallas']:.3e} <= {GRID_ORACLE_TOL}; max |pallas-vmap| "
           f"{gap:.3e} <= {PALLAS_VMAP_TOL}")


def run_phases(size: Size) -> None:
    w, session = phase_profiles(size)
    phase_exact_lru(w, session)
    phase_service(size, session)
    phase_sweep(size, w, session)


def main() -> int:
    from repro.compile_cache import cache_entries, enable_compile_cache

    device = check_device()
    cache = enable_compile_cache()
    print(f"[cache] {cache}: {cache_entries(cache)} entries at start",
          flush=True)
    t0 = time.perf_counter()
    run_phases(SIZES["full"])
    print(f"[total] {time.perf_counter() - t0:.3f}s", flush=True)
    print(f"[cache] {cache}: {cache_entries(cache)} entries at end",
          flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
