"""Cold profile builds: what every workload pays on its first request.

Traffic parameters (``bench/traffic/<mix>.json``):

* ``workloads``: the configuration's workloads that arrive cold.

The window builds the mix's workloads in whole passes, each pass in an
order drawn from the seed, and ends at the end of the pass in which
``--seconds`` is crossed: every seed builds the same set.  Each build
is a fresh ``Session`` with no store: ``load`` (trace generation), then
``artifacts`` for every core count (reuse distance, mimicry,
interleaving, profiles), then ``predict`` for every machine (SDCM grid
on the device, Eq. 4-7 on the host).

Set-up builds nothing: it compiles the SDCM grid programs that the
window's ``predict`` calls use, from the profile lengths that the
configuration states for every workload and core count.

Every build of the window is compared with the reference: the trace's
digest, every profile (exactly), every hit rate and runtime.
"""
from __future__ import annotations

import time

import numpy as np

from bench.common import Reference, config_departures, gap, rel_gap, \
    resolve_source, trace_arrays, trace_digest
from bench.harness import Check


def _request(config: dict, source):
    from repro.api import PredictionRequest

    return PredictionRequest(
        targets=tuple(m["name"] for m in config["machines"]),
        core_counts=tuple(config["cores"]),
        strategies=(config["strategy"],),
        counts=source.op_counts,
    )


def build(config: dict, source, ctx=None) -> dict:
    """One cold build through the program; returns its answers."""
    import contextlib

    from repro.api import AnalyticalSDCM, Session

    span = ctx.span if ctx is not None else (
        lambda _n: contextlib.nullcontext())
    session = Session(cache_model=AnalyticalSDCM(backend="batched"))
    with span("load"):
        _tid, trace = session.load(source)
    with span("artifacts"):
        arts = {c: session.artifacts(source, c, strategy=config["strategy"],
                                     line_size=config["line_size"])
                for c in config["cores"]}
    with span("predict"):
        result = session.predict(source, _request(config, source))
    return {
        "refs": len(trace),
        "profiles": {c: {"prd": (a.prd.distances, a.prd.counts),
                         "crd": (a.crd.distances, a.crd.counts)}
                     for c, a in arts.items()},
        "cells": {(p.target, p.cores): (
            [p.hit_rates[lv["name"]] for lv in _levels(config, p.target)],
            p.t_pred_s) for p in result},
    }


def _levels(config: dict, machine: str) -> list[dict]:
    return next(m for m in config["machines"]
                if m["name"] == machine)["levels"]


def arriving(config: dict, traffic: dict) -> list[str]:
    """The workloads of the mix, in the configuration's order."""
    names = [w["name"] for w in config["workloads"]]
    wanted = traffic["workloads"]
    unknown = set(wanted) - set(names)
    if unknown:
        raise KeyError(f"mix names workloads the configuration lacks: "
                       f"{sorted(unknown)}")
    return [n for n in names if n in wanted]


def warm_grid(config: dict, sources: dict) -> None:
    """Run the SDCM grid of every workload's ``predict`` once on
    profiles of the lengths the configuration states, which gives the
    grid the row shapes (padded lengths, row counts, associativity
    buckets) that the window's builds give it."""
    from types import SimpleNamespace

    from repro.api import AnalyticalSDCM
    from repro.core.reuse.profile import ReuseProfile

    def profile(n: int) -> ReuseProfile:
        return ReuseProfile(np.arange(n, dtype=np.int64),
                            np.ones(n, dtype=np.int64), n)

    model = AnalyticalSDCM(backend="batched")
    for w in config["workloads"]:
        if w["name"] not in sources:
            continue
        lengths = w["profile_lengths"]
        items = [(cell.target, SimpleNamespace(
                      prd=profile(lengths[str(cell.cores)][0]),
                      crd=profile(lengths[str(cell.cores)][1])))
                 for cell in _request(config, sources[w["name"]]).cells()]
        model.hit_rates_grid(items)


def setup(config: dict, traffic: dict, ctx) -> dict:
    sources = {w["name"]: resolve_source(w) for w in config["workloads"]
               if w["name"] in arriving(config, traffic)}
    with ctx.span("warmup"):
        warm_grid(config, sources)
    return {"config": config, "sources": sources, "answers": []}


def window(state: dict, ctx) -> None:
    rng = np.random.default_rng(ctx.seed)
    names = list(state["sources"])
    answers = state["answers"]
    t0 = time.perf_counter()
    passes = 0
    while True:
        for i in rng.permutation(len(names)):
            answers.append((names[i], build(state["config"],
                                            state["sources"][names[i]], ctx)))
        passes += 1
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    elapsed = time.perf_counter() - t0
    refs = sum(a["refs"] for _n, a in answers)
    ctx.records.update(
        refs=refs, elapsed_s=elapsed, attempted=len(answers), failed=0,
        log=[f"[window] {len(answers)} cold builds in {passes} passes "
             f"({', '.join(n for n, _a in answers)}), {refs} refs, "
             f"{elapsed:.3f} s; closed loop, generator lateness 0"],
    )


def reference_cells(config: dict, ref: Reference, counts: dict,
                    dtype=np.float64) -> dict:
    """(machine, cores) -> (hit rates, Eq. 4-7 runtime), from the
    reference; a lower ``dtype`` gives the control's hit rates."""
    from bench.reference import runtime

    out = {}
    for m in config["machines"]:
        for c in config["cores"]:
            rates = ref.machine_rates(m, c, dtype)
            out[(m["name"], c)] = (rates,
                                   runtime.eq_runtime_s(m, rates, counts, c))
    return out


def compare(config: dict, answers, refs: dict, ref_cells: dict
            ) -> list[Check]:
    limits = config["limits"]
    profile_bad = 0
    rate_gap = runtime_gap = 0.0
    for name, a in answers:
        for c, pair in a["profiles"].items():
            want = refs[name].profiles(c)
            for which in ("prd", "crd"):
                got = pair[which]
                if not (np.array_equal(got[0], want[which][0])
                        and np.array_equal(got[1], want[which][1])):
                    profile_bad += 1
        for key, (rates, t) in ref_cells[name].items():
            got = a["cells"].get(key)
            if got is None:
                rate_gap = runtime_gap = float("inf")
                continue
            rate_gap = max(rate_gap, gap(got[0], rates))
            runtime_gap = max(runtime_gap, rel_gap([got[1]], [t]))
    return [Check("profiles_unequal", float(profile_bad), 0.0),
            Check("hit_rate_gap", rate_gap, limits["hit_rate_gap"]),
            Check("runtime_rel_gap", runtime_gap, limits["runtime_rel_gap"])]


def references(config: dict, sources: dict, built: set
               ) -> tuple[dict, list[str]]:
    """The reference of every workload built, and where the program's
    inputs depart from the configuration."""
    departures = config_departures(config, sources)
    refs = {}
    for w in config["workloads"]:
        if w["name"] not in built:
            continue
        arrays = trace_arrays(sources[w["name"]].trace())
        if trace_digest(arrays) != w["trace_sha256"]:
            departures.append(f"trace of {w['name']}")
        refs[w["name"]] = Reference(arrays, config["line_size"])
    return refs, departures


def check(state: dict, ctx) -> list[Check]:
    config = state["config"]
    refs, departures = references(config, state["sources"],
                                  {n for n, _a in state["answers"]})
    for d in departures:
        print(f"[check] departs from the configuration: {d}")
    counts = {w["name"]: w["op_counts"] for w in config["workloads"]}
    ref_cells = {n: reference_cells(config, r, counts[n])
                 for n, r in refs.items()}
    state.update(refs=refs, ref_cells=ref_cells)
    return [Check("config_departures", float(len(departures)), 0.0)] + \
        compare(config, state["answers"], refs, ref_cells)


def control_cells(config: dict, refs: dict) -> dict:
    """The reference's cells with SDCM in bfloat16, the next precision
    below the float32 that the configuration states."""
    import ml_dtypes

    counts = {w["name"]: w["op_counts"] for w in config["workloads"]}
    return {n: reference_cells(config, r, counts[n], ml_dtypes.bfloat16)
            for n, r in refs.items()}


def control(state: dict) -> list[Check]:
    """The control in the program's place: exact reference profiles,
    bfloat16 hit rates."""
    config, refs = state["config"], state["refs"]
    low = control_cells(config, refs)
    answers = [(n, {"profiles": {c: refs[n].profiles(c)
                                 for c in config["cores"]},
                    "cells": low[n]}) for n in refs]
    return compare(config, answers, refs, state["ref_cells"])
