"""Helpers of the benchmark's tests: the repository root on the path
(``bench`` is imported as a package) and cells cut to the ``smoke``
preset, which the CPU can run in seconds."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def smoke_cell(name: str, workloads: int = 3):
    """A cell of ``BENCHMARK.json`` at the smoke preset: the first
    ``workloads`` workloads of its mix, a small sweep space, and the
    trace digests, operation counts and profile lengths of the smoke
    traces."""
    from bench import harness
    from bench.common import OP_CLASSES, Reference, trace_arrays, \
        trace_digest
    from repro.workloads import registry

    cell = harness.load_cell(name)
    cfg = copy.deepcopy(cell.config)
    wanted = cell.traffic.get("workloads")
    cfg["workloads"] = [w for w in cfg["workloads"]
                        if wanted is None or w["name"] in wanted][:workloads]
    if wanted is not None:
        cell.traffic = dict(cell.traffic,
                            workloads=[w["name"] for w in cfg["workloads"]])
    for w in cfg["workloads"]:
        src = registry.resolve(w["name"], "smoke")
        arrays = trace_arrays(src.trace())
        ref = Reference(arrays, cfg["line_size"])
        w.pop("sizes", None)
        w.update(preset="smoke", trace_sha256=trace_digest(arrays),
                 op_counts={k: float(getattr(src.op_counts, k))
                            for k in OP_CLASSES})
        if "profile_lengths" in w:
            w["profile_lengths"] = {
                str(c): [len(ref.profiles(c)[k][0]) for k in ("prd", "crd")]
                for c in cfg["cores"]}
    if "space" in cfg:
        cfg["space"].update(sets=[64, 256], ways=[1, 4, 20])
    cell.config = cfg
    return cell
