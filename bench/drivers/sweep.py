"""Config sweeps through ``repro.explore``'s fused evaluator.

Traffic parameters (``bench/traffic/<mix>.json``):

* ``inner``: the sweep's inner evaluator (``vmap`` or ``pallas``).

Every call scores the configuration's whole space, in an order drawn
from the seed; no answer is reused between calls.  The window ends at
the end of the call that crosses ``--seconds``.
Every config scored in it is compared with the float64 reference:
its hit rate at every level and its ECM runtime.
"""
from __future__ import annotations

import time

import numpy as np

from bench.common import Reference, config_departures, gap, rel_gap, \
    resolve_source, trace_arrays, trace_digest
from bench.harness import Check
from bench.reference import runtime, sdcm


def setup(config: dict, traffic: dict, ctx) -> dict:
    from repro.api import Session
    from repro.explore import SearchSpace
    from repro.explore.engine import FusedSweepEvaluator

    (entry,) = config["workloads"]
    source = resolve_source(entry)
    space = SearchSpace(**config["space"])
    session = Session(cache_model="batched")
    evaluator = FusedSweepEvaluator(source, space, session=session,
                                    inner=traffic["inner"])
    configs = space.configs()
    state = {"config": config, "traffic": traffic, "source": source,
             "space": space, "evaluator": evaluator, "configs": configs,
             "answers": []}
    with ctx.span("warmup"):
        evaluator.evaluate(configs)
    return state


def window(state: dict, ctx) -> None:
    rng = np.random.default_rng(ctx.seed)
    evaluator, configs = state["evaluator"], state["configs"]
    answers = state["answers"]

    t0 = time.perf_counter()
    while True:
        order = rng.permutation(len(configs))
        with ctx.span("sweep"):
            res = evaluator.evaluate([configs[i] for i in order])
        answers.append((order, res.rates, res.t_pred_s))
        ctx.count("configs", len(order))
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    elapsed = time.perf_counter() - t0
    n = int(ctx.counters.get("configs", 0))
    ctx.records.update(
        configs=n, elapsed_s=elapsed, attempted=n,
        failed=sum(int(np.sum(~np.isfinite(r).all(axis=1))) + int(
            np.sum(~np.isfinite(t))) for _i, r, t in answers),
        log=[f"[window] {n} configs in {len(answers)} calls, "
             f"{elapsed:.3f} s; closed loop, generator lateness 0"],
    )


def reference_answers(state: dict, dtype=np.float64
                      ) -> tuple[np.ndarray, np.ndarray]:
    """Hit rates [C, L] and ECM runtimes [C] of every config of the
    space, in ``state['configs']`` order, from the reference."""
    config = state["config"]
    (entry,) = config["workloads"]
    (machine,) = config["machines"]
    if "reference" not in state:
        state["reference"] = Reference(
            trace_arrays(state["source"].trace()), config["line_size"])
    ref = state["reference"]
    space = config["space"]
    swept = next(i for i, lv in enumerate(machine["levels"])
                 if lv["name"] == space["level"])
    shared = machine["shared_level"] % len(machine["levels"])
    configs = state["configs"]
    n_levels = len(machine["levels"])
    rates = np.zeros((len(configs), n_levels), dtype=dtype)
    beta = np.zeros((len(configs), n_levels), dtype=np.float64)
    for ci, c in enumerate(configs):
        for lv, level in enumerate(machine["levels"]):
            if lv == swept:
                geom = {"size_bytes": c.sets * c.ways * c.line_size,
                        "line_size": c.line_size, "assoc": c.ways}
            else:
                geom = dict(level, line_size=c.line_size)
            assoc, blocks = sdcm.level_geometry(geom)
            rates[ci, lv] = ref.rate(c.cores, "crd" if lv >= shared else
                                     "prd", assoc, blocks, dtype)
        beta[ci] = machine["level_beta_cy"][1:] + [machine["ram_beta_cy"]]
        if swept >= 1:
            beta[ci, swept - 1] = c.beta_cy
    cores = np.array([c.cores for c in configs], dtype=np.float64)
    t = runtime.ecm_runtime_s(machine, rates, entry["op_counts"], cores,
                              beta, dtype)
    return rates, t


def compare(state: dict, answers, ref_rates, ref_t) -> list[Check]:
    limits = state["config"]["limits"]
    rate_gap = runtime_gap = 0.0
    missing = 0
    for idx, rates, t in answers:
        if rates is None or t is None or len(idx) == 0:
            missing += 1
            continue
        rate_gap = max(rate_gap, gap(rates, ref_rates[idx]))
        runtime_gap = max(runtime_gap, rel_gap(t, ref_t[idx]))
    return [Check("hit_rate_gap", rate_gap, limits["hit_rate_gap"]),
            Check("runtime_rel_gap", runtime_gap,
                  limits["runtime_rel_gap"]),
            Check("calls_unanswered", float(missing), 0.0)]


def check(state: dict, ctx) -> list[Check]:
    config = state["config"]
    (entry,) = config["workloads"]
    arrays = trace_arrays(state["source"].trace())
    departures = config_departures(config, {entry["name"]: state["source"]})
    if trace_digest(arrays) != entry["trace_sha256"]:
        departures.append(f"trace of {entry['name']}")
    for d in departures:
        print(f"[check] departs from the configuration: {d}")
    state.pop("evaluator")
    ref_rates, ref_t = reference_answers(state)
    return [Check("config_departures", float(len(departures)), 0.0)] + \
        compare(state, state["answers"], ref_rates, ref_t)


def control(state: dict) -> list[Check]:
    """The reference in bfloat16, in the program's place, judged as the
    program's answers are (the next precision below the float32 that
    the configuration states)."""
    import ml_dtypes

    ref_rates, ref_t = reference_answers(state)
    low_rates, low_t = reference_answers(state, ml_dtypes.bfloat16)
    every = np.arange(len(state["configs"]))
    return compare(state, [(every, low_rates.astype(np.float64),
                            low_t.astype(np.float64))], ref_rates, ref_t)
