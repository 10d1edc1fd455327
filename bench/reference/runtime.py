"""Runtime models as the paper and the ECM papers state them, in float64.

``machine`` is a machine entry of a configuration file: cache levels,
per-level latency and reciprocal throughput (cycles), RAM latency and
throughput, clock, the aggregate instruction timings of Eq. 4-7 and
the per-class port table of the ECM model.  ``counts`` holds the
operation counts of a workload (int, fp, div, loads, stores, bytes).
"""
from __future__ import annotations

import numpy as np

COMPUTE = ("int_ops", "fp_ops", "div_ops")


def _chain(values, rates, final):
    acc = final
    for p, v in zip(reversed(rates), reversed(values)):
        acc = p * v + (1.0 - p) * acc
    return acc


def eq_runtime_s(machine: dict, rates: list[float], counts: dict,
                 cores: int) -> float:
    """Paper Eq. 4-7, throughput mode, contiguous blocks: T_mem from the
    latency and throughput chains over the cumulative hit rates, T_CPU
    as one latency plus (n - 1) reciprocal throughputs per class, both
    on each core's even share of the work."""
    share = 1.0 / max(cores, 1)
    delta = _chain(machine["level_latency_cy"], rates,
                   machine["ram_latency_cy"])
    beta = _chain(machine["level_beta_cy"], rates, machine["ram_beta_cy"])
    b = float(machine["word_bytes"])
    cycle = 1.0 / machine["freq_hz"]
    t_mem = (delta + (b - 1.0) * beta) / b * counts["total_bytes"] * share \
        * cycle
    cy = 0.0
    for cls in COMPUTE:
        n = counts[cls] * share
        if n > 0:
            t = machine["instr"][cls]
            cy += t["delta"] + max(n - 1.0, 0.0) * t["beta"]
    return t_mem + cy * cycle


def ecm_runtime_s(machine: dict, rates: np.ndarray, counts: dict,
                  cores: np.ndarray, trans_beta: np.ndarray,
                  dtype=np.float64) -> np.ndarray:
    """ECM, throughput mode, vectorized over configs.

    ``rates`` is [C, L] cumulative hit rates, ``cores`` [C] and
    ``trans_beta`` [C, L] the reciprocal throughput of each boundary
    (boundary i moves what missed level i into level i + 1, RAM last).
    Per core: max(compute ports, load/store issue + every boundary's
    transfers) on a 1/cores share; chip wide, the transfers into the
    shared level and beyond on the whole traffic; the larger wins.
    """
    port = machine["incore"]
    rates = np.asarray(rates, dtype=dtype)
    share = dtype(1.0) / np.maximum(np.asarray(cores, dtype=dtype),
                                    dtype(1.0))
    comp = max(counts[c] * port[c]["beta"] / port[c]["ports"]
               for c in COMPUTE)
    lsu = (counts["loads"] * port["loads"]["beta"] / port["loads"]["ports"]
           + counts["stores"] * port["stores"]["beta"]
           / port["stores"]["ports"])
    mem_ops = dtype(counts["loads"] + counts["stores"])
    reach = np.minimum.accumulate(np.clip(dtype(1.0) - rates, 0.0, 1.0),
                                  axis=1)
    transfers = mem_ops * reach * np.asarray(trans_beta, dtype=dtype)
    core = np.maximum(dtype(comp) * share,
                      dtype(lsu) * share + share * transfers.sum(axis=1))
    shared = machine["shared_level"] % len(machine["levels"])
    sat = transfers[:, max(shared - 1, 0):].sum(axis=1)
    return (np.maximum(core, sat) / dtype(machine["freq_hz"])).astype(dtype)
