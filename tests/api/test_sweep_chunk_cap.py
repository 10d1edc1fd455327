"""The sweep's chunk cap: every dispatch evaluates at most
``SWEEP_MAX_ELEMS`` padded row-distance elements, however wide the
profiles, and chunking leaves every config's bits alone."""
from __future__ import annotations

import numpy as np
import pytest

from repro import telemetry
from repro.api import Session, batched
from repro.api.batched import batched_hit_rates
from repro.api.stages import shared_level_index
from repro.core import sdcm
from repro.explore import FusedSweepEvaluator, SearchSpace
from repro.workloads import registry

SPACE = SearchSpace(
    target="Xeon E5-2699 v4", level="L3", sets=(64, 128, 256, 512),
    ways=(1, 2, 3, 4, 5, 6, 7, 8, 9, 20), cores=(1, 2),
    strategies=("round_robin",),
)


@pytest.fixture(scope="module")
def canneal():
    source = registry.resolve("parsec/canneal", "smoke")
    session = Session(cache_model="batched")
    return source, session


@pytest.fixture(autouse=True)
def clean_recorder():
    telemetry.reset()
    yield
    telemetry.reset()


@pytest.mark.parametrize("rows_per_cap", [2, 4, 16])
def test_every_dispatch_stays_under_the_cap(canneal, monkeypatch,
                                            rows_per_cap):
    source, session = canneal
    ev = FusedSweepEvaluator(source, SPACE, session=session)
    configs = SPACE.configs()
    ev.evaluate(configs[:1])                      # pack the profiles
    max_m = max(max(p.m for p in pack) for pack in ev._packs.values())
    cap = rows_per_cap * max_m
    monkeypatch.setattr(batched, "SWEEP_MAX_ELEMS", cap)
    seen: list[tuple] = []
    real = batched._record_signature
    monkeypatch.setattr(batched, "_record_signature",
                        lambda sig: seen.append(sig) or real(sig))
    before = ev.stats.fused_dispatches
    with telemetry.enable():
        res = ev.evaluate(configs)
    snap = telemetry.snapshot()

    sweeps = [s for s in seen if s[0] == "sweep"]
    assert len(sweeps) == ev.stats.fused_dispatches - before
    elems = [g * max(prd_m, crd_m) for *_k, g, prd_m, crd_m in sweeps]
    assert max(elems) <= cap
    assert snap["sdcm.eval"]["count"] == len(sweeps)
    assert snap["sdcm.eval"]["n"] == sum(elems)

    items = []
    base = ev.base
    for cfg in configs:
        art = session.artifacts(source, cfg.cores, strategy=cfg.strategy,
                                line_size=cfg.line_size)
        items.append((cfg.apply(base, ev.level_idx), art))
    oracle = batched_hit_rates(items)
    names = [lvl.name for lvl in base.levels]
    assert res.rates.tolist() == [[r[n] for n in names] for r in oracle]
    for ci, (target, art) in enumerate(items):
        shared = shared_level_index(target)
        for lv, lvl in enumerate(target.levels):
            prof = art.crd if lv >= shared else art.prd
            want = sdcm.hit_rate(prof, lvl.effective_assoc, lvl.num_lines)
            assert abs(res.rates[ci, lv] - want) <= 1e-6


def test_cap_leaves_narrow_profiles_in_one_chunk(canneal):
    """At the real cap, a narrow profile's group goes out whole: one
    dispatch per (cores, bucket) group."""
    source, session = canneal
    ev = FusedSweepEvaluator(source, SPACE, session=session)
    configs = SPACE.configs()
    ev.evaluate(configs)
    buckets = {(c.cores, batched._bucket(c.ways)) for c in configs}
    assert ev.stats.fused_dispatches == len(buckets)
