"""Benchmark orchestrator — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full | --smoke]

* paper_hit_rates  — Figs. 5-6 analog (SDCM vs exact LRU, 3 CPU targets)
* paper_runtimes   — Figs. 8-10 analog (Eq. 4-7 vs exact-rate runtimes)
* reuse_throughput — §3.3.1 (tree vs stack reuse-profile throughput)
  + the Session-vs-legacy grid timing (BENCH_api_grid.json)
  + the batched-fused profile-build benchmark (BENCH_profile.json;
    standalone via ``-m benchmarks.reuse_throughput --profile-gate``)
* roofline_table   — §Roofline (the cell table from the dry-run records)
* service_load     — coalesced PredictionService vs naive per-request
  loop at 1/8/64 concurrent clients (BENCH_service.json)
* explore_sweep    — fused device-resident config sweep vs per-config
  Session.predict loop (BENCH_explore.json; standalone via
  ``-m benchmarks.explore_sweep --smoke``)

``--smoke`` runs a minimal Session grid + the api-grid timing only —
the CI sanity job.
"""
from __future__ import annotations

import sys
import time


def smoke() -> int:
    """CI smoke: tiny end-to-end grid through repro.api + grid timing."""
    from benchmarks.reuse_throughput import api_grid_benchmark
    from repro.api import PredictionRequest, Session
    from repro.hw.targets import CPU_TARGETS
    from repro.workloads.polybench import make_atax

    w = make_atax(n=32)
    session = Session()
    result = session.predict(
        w,
        PredictionRequest(
            targets=tuple(CPU_TARGETS) + ("tpu-v5e",),
            core_counts=(1, 2, 4),
            counts=w.op_counts,
        ),
    )
    print(result.to_table())
    assert len(result) == 12 and all(p.t_pred_s > 0 for p in result)
    grid = api_grid_benchmark(n=32, core_counts=(1, 2, 4))
    assert grid["speedup"] > 1.0, grid
    print("SMOKE-OK")
    return 0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if "--smoke" in argv:
        return smoke()
    quick = "--full" not in argv
    t0 = time.time()
    print("=" * 72)
    print(f"PPT-Multicore-on-TPU benchmark suite "
          f"({'quick' if quick else 'full'} mode)")
    print("=" * 72)

    from benchmarks import (
        explore_sweep, paper_hit_rates, paper_runtimes, reuse_throughput,
        roofline_table, service_load,
    )

    print("\n### [1/6] cache hit rates: SDCM prediction vs exact LRU "
          "(paper Figs. 5-6)\n")
    hr = paper_hit_rates.run(quick=quick)

    print("\n### [2/6] runtime prediction: Eq. 4-7 (paper Figs. 8-10)\n")
    rt = paper_runtimes.run(quick=quick)

    print("\n### [3/6] reuse-profile throughput (paper §3.3.1) + "
          "batched-fused profile builds\n")
    reuse_throughput.run(quick=quick)

    print("\n### [4/6] roofline table from dry-run records (§Roofline)\n")
    try:
        roofline_table.run("pod")
    except Exception as e:  # records may not exist yet
        print(f"  (roofline table unavailable: {e})")

    print("\n### [5/6] prediction-service throughput: coalesced vs "
          "naive per-request loop\n")
    service_load.run(quick=quick)

    print("\n### [6/6] fused config sweep vs per-config predict loop "
          "(repro.explore)\n")
    explore_sweep.run(quick=quick)

    print("\n" + "=" * 72)
    print(f"hit-rate avg |err| {hr['overall_avg_abs_err_pct']:.2f}% "
          f"(paper claim 1.23%) | runtime avg err "
          f"{rt['overall_avg_rel_err_pct']:.2f}% (paper claim 9.08%) | "
          f"total {time.time() - t0:.0f}s")
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
