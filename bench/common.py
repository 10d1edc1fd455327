"""Pieces the drivers share: the run's inputs checked against the
configuration, and the reference's answers for one workload."""
from __future__ import annotations

import hashlib

import numpy as np

from bench.reference import reuse, sdcm

OP_CLASSES = ("int_ops", "fp_ops", "div_ops", "loads", "stores",
              "total_bytes")


def resolve_source(entry: dict):
    """The program's source of one workload of a configuration: a
    registry preset (``preset``) or the generator's own sizes
    (``sizes``, passed to ``repro.workloads.polybench.MAKERS``)."""
    if "sizes" in entry:
        from repro.workloads.polybench import MAKERS

        return MAKERS[entry["name"].split("/")[-1]](**entry["sizes"])
    from repro.workloads import registry

    return registry.resolve(entry["name"], entry["preset"])


def trace_arrays(trace) -> dict:
    return {"addresses": np.asarray(trace.addresses, dtype=np.int64),
            "bb_ids": np.asarray(trace.bb_ids, dtype=np.int32),
            "inst_ids": np.asarray(trace.inst_ids, dtype=np.int64),
            "shared_mask": np.asarray(trace.shared_mask, dtype=np.bool_)}


def trace_digest(arrays: dict) -> str:
    """sha256 over the four label arrays, in a fixed order and dtype."""
    h = hashlib.sha256()
    for key in ("addresses", "bb_ids", "inst_ids", "shared_mask"):
        h.update(np.ascontiguousarray(arrays[key]).tobytes())
    return h.hexdigest()


def machine_of(target) -> dict:
    """A program target in the configuration files' terms."""
    instr = target.instr
    return {
        "name": target.name, "freq_hz": target.freq_hz,
        "levels": [{"name": lv.name, "size_bytes": lv.size_bytes,
                    "line_size": lv.line_size, "assoc": lv.assoc}
                   for lv in target.levels],
        "level_latency_cy": list(target.level_latency_cy),
        "level_beta_cy": list(target.level_beta_cy),
        "ram_latency_cy": target.ram_latency_cy,
        "ram_beta_cy": target.ram_beta_cy,
        "word_bytes": target.word_bytes,
        "shared_level": target.shared_level,
        "instr": {"int_ops": {"delta": instr.delta_int,
                              "beta": instr.beta_int},
                  "fp_ops": {"delta": instr.delta_fp, "beta": instr.beta_fp},
                  "div_ops": {"delta": instr.delta_div,
                              "beta": instr.beta_div}},
        "incore": {cls: {"delta": getattr(target.incore, cls).delta,
                         "beta": getattr(target.incore, cls).beta,
                         "ports": getattr(target.incore, cls).ports}
                   for cls in ("int_ops", "fp_ops", "div_ops", "loads",
                               "stores")},
    }


def config_departures(config: dict, sources: dict) -> list[str]:
    """Where the program's inputs differ from what the configuration
    states: machine parameters, trace contents, operation counts."""
    from repro.hw.targets import resolve_target

    out = []
    for m in config["machines"]:
        if machine_of(resolve_target(m["name"])) != m:
            out.append(f"machine {m['name']}")
    for w in config["workloads"]:
        if w["name"] not in sources:
            continue
        src = sources[w["name"]]
        counts = {k: float(getattr(src.op_counts, k)) for k in OP_CLASSES}
        if counts != w["op_counts"]:
            out.append(f"op counts of {w['name']}")
    return out


class Reference:
    """The reference's profiles and hit rates for one workload's trace,
    each computed once."""

    def __init__(self, arrays: dict, line_size: int):
        self.arrays = arrays
        self.line_size = line_size
        self._profiles: dict[int, dict] = {}
        self._rows: dict = {}

    def profiles(self, cores: int) -> dict:
        if cores not in self._profiles:
            self._profiles[cores] = reuse.cell_profiles(
                self.arrays, cores, self.line_size)
        return self._profiles[cores]

    def rate(self, cores: int, which: str, assoc: int, blocks: int,
             dtype=np.float64) -> float:
        key = (cores, which, assoc, blocks, np.dtype(dtype).name)
        if key not in self._rows:
            values, counts = self.profiles(cores)[which]
            self._rows[key] = sdcm.hit_rate(values, counts, assoc, blocks,
                                            dtype)
        return self._rows[key]

    def machine_rates(self, machine: dict, cores: int,
                      dtype=np.float64) -> list[float]:
        shared = machine["shared_level"] % len(machine["levels"])
        out = []
        for i, level in enumerate(machine["levels"]):
            assoc, blocks = sdcm.level_geometry(level)
            out.append(self.rate(cores, "crd" if i >= shared else "prd",
                                 assoc, blocks, dtype))
        return out


def gap(a, b) -> float:
    """Largest absolute difference; inf where a value is missing."""
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    if a.shape != b.shape or not np.all(np.isfinite(a)):
        return float("inf")
    return float(np.max(np.abs(a - b), initial=0.0))


def rel_gap(a, b) -> float:
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    if a.shape != b.shape or not np.all(np.isfinite(a)):
        return float("inf")
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-300),
                        initial=0.0))
