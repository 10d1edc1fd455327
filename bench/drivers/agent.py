"""Search agents scoring a few configs per round through
``repro.explore``'s fused evaluator.

Traffic parameters (``bench/traffic/<mix>.json``):

* ``agent``: the agent of ``repro.explore.agents`` (``hillclimb``), and
  its ``max_rounds``;
* ``inner``: the sweep's inner evaluator (``vmap`` or ``pallas``).

Climbs run back to back, closed loop, each with a fresh ``ScoreCache``
over the one evaluator and a start drawn from the seed, until the
evaluator call that crosses ``--seconds``.  A round scores the current
point and its unscored neighbours.  Every config the evaluator scored
is compared with the float64 reference, as in ``sweep``, whose check
and control this driver shares.
"""
from __future__ import annotations

import time

import numpy as np

from bench.common import resolve_source
from bench.drivers.sweep import check, control  # noqa: F401


def setup(config: dict, traffic: dict, ctx) -> dict:
    from repro.api import Session
    from repro.explore import SearchSpace
    from repro.explore.engine import FusedSweepEvaluator

    (entry,) = config["workloads"]
    source = resolve_source(entry)
    space = SearchSpace(**config["space"])
    evaluator = FusedSweepEvaluator(source, space,
                                    session=Session(cache_model="batched"),
                                    inner=traffic["inner"])
    configs = space.configs()
    state = {"config": config, "traffic": traffic, "source": source,
             "space": space, "evaluator": evaluator, "configs": configs,
             "answers": []}
    with ctx.span("warmup"):
        warm(evaluator, configs, round_size(space))
    return state


def round_size(space) -> int:
    """The most configs one round proposes: the current point and a
    step either way along every axis with more than one value."""
    return 1 + 2 * sum(n > 1 for n in space.axis_sizes())


def warm(evaluator, configs, most: int) -> None:
    """Compile every shape a round can dispatch: each of the sweep's
    row groups (cores, per-level bucket) at every padded row count of
    up to ``most`` rows."""
    from repro.api.batched import _pow2, _sweep_akey

    groups: dict[tuple, list] = {}
    for c in configs:
        geom = evaluator._geometry([c], c.line_size, c.cores)
        key = (c.line_size, c.cores, c.strategy,
               _sweep_akey(geom.assoc[0], geom.blocks[0]))
        groups.setdefault(key, []).append(c)
    for rows in sorted({_pow2(n) for n in range(1, most + 1)}):
        evaluator.evaluate([c for group in groups.values()
                            for c in group[:rows]])


def window(state: dict, ctx) -> None:
    from repro.explore.agents import ScoreCache, Trajectory, make_agent

    traffic = state["traffic"]
    rng = np.random.default_rng(ctx.seed)
    evaluator, space = state["evaluator"], state["space"]
    index = {c.key(): i for i, c in enumerate(state["configs"])}
    answers = state["answers"]
    agent = make_agent(traffic["agent"],
                       {"max_rounds": traffic["max_rounds"]})

    t0 = time.perf_counter()
    crossed = False

    def score(batch, cache):
        nonlocal crossed
        res = evaluator.evaluate(batch)
        answers.append((np.array([index[c.key()] for c in batch]),
                        res.rates, res.t_pred_s))
        ctx.count("configs", len(batch))
        if time.perf_counter() - t0 >= ctx.seconds:
            crossed = True
            cache.budget = 0              # the agent stops at its next round
        return res.scores

    climbs = 0
    while not crossed:
        cache = ScoreCache(lambda batch: score(batch, cache), len(index),
                           Trajectory(agent=agent.name, seed=ctx.seed))
        # one span per climb, not per round: a traced window's idle gaps
        # are labelled by a scan over every span
        with ctx.span("climb"):
            agent.search(space, cache, rng)
        climbs += 1
    elapsed = time.perf_counter() - t0
    n = int(ctx.counters.get("configs", 0))
    ctx.records.update(
        configs=n, elapsed_s=elapsed, attempted=n,
        failed=sum(int(np.sum(~np.isfinite(r).all(axis=1))) + int(
            np.sum(~np.isfinite(t))) for _i, r, t in answers),
        log=[f"[window] {n} configs in {len(answers)} rounds of "
             f"{climbs} climbs, {elapsed:.3f} s; closed loop"],
    )
