"""Fused config sweep vs the per-config oracle paths.

The acceptance bar: sweep hit rates are BIT-identical to
`batched_hit_rates` evaluating each candidate target row-by-row, the
on-device ECM chain matches the host `ECMRuntimeModel`, and the Pallas
inner evaluator agrees with the vmap inner to 1e-6.
"""
from __future__ import annotations

from collections import Counter

import numpy as np
import pytest

import jax

from repro import telemetry
from repro.api import Session, batched
from repro.api.batched import batched_hit_rates, compile_count
from repro.api.stages import shared_level_index
from repro.core.incore import ECMRuntimeModel, t_comp_cy, t_lsu_cy
from repro.core.runtime_model import OpCounts
from repro.core.trace.types import trace_from_blocks
from repro.explore import FusedSweepEvaluator, SearchSpace
from repro.hw.targets import resolve_target

COUNTS = OpCounts(int_ops=3000, fp_ops=1500, div_ops=10, loads=3000,
                  stores=1500, total_bytes=4500 * 8)

SPACE = SearchSpace(
    sets=(512, 4096), ways=(4, 8), latency_cy=(20.0, 36.0),
    cores=(1, 2), strategies=("round_robin",),
)


def small_trace(iters=600, stride=8):
    blocks = [("OUT__1__.entry", np.array([0, 8]), True)]
    A0, B0 = 1 << 20, 2 << 20
    for i in range(iters):
        blocks.append((
            "OUT__1__.for.body",
            np.array([A0 + stride * i, B0 + stride * (i % 64), 0]),
            np.array([False, False, True]),
        ))
    return trace_from_blocks(blocks)


@pytest.fixture(scope="module")
def sweep_setup():
    source = small_trace()
    session = Session(cache_model="batched")
    evaluator = FusedSweepEvaluator(
        source, SPACE, session=session, counts=COUNTS,
    )
    return source, session, evaluator


def oracle_items(session, source, evaluator, configs):
    """The sequential path: one applied target + artifact set per
    candidate, exactly what `Session.predict` would evaluate."""
    base = evaluator.base
    li = evaluator.level_idx
    items = []
    for cfg in configs:
        art = session.artifacts(
            source, cfg.cores, strategy=cfg.strategy, seed=0,
            line_size=cfg.line_size,
        )
        items.append((cfg.apply(base, li), art))
    return items


def test_sweep_rates_bit_identical_to_batched_hit_rates(sweep_setup):
    source, session, evaluator = sweep_setup
    configs = SPACE.configs()
    assert len(configs) >= 8
    res = evaluator.evaluate(configs)

    items = oracle_items(session, source, evaluator, configs)
    oracle = batched_hit_rates(items)
    level_names = [lvl.name for lvl in evaluator.base.levels]
    for ci, per_level in enumerate(oracle):
        want = np.array([per_level[n] for n in level_names])
        got = res.rates[ci]
        assert got.tolist() == want.tolist(), (
            f"config {configs[ci]} rates diverge from the oracle"
        )


def test_sweep_runtime_matches_host_ecm(sweep_setup):
    source, session, evaluator = sweep_setup
    configs = SPACE.configs()
    res = evaluator.evaluate(configs)
    assert res.t_pred_s is not None and np.all(res.t_pred_s > 0)

    model = ECMRuntimeModel()
    items = oracle_items(session, source, evaluator, configs)
    for ci, ((target, _art), per_level) in enumerate(
        zip(items, batched_hit_rates(items))
    ):
        host = model.runtime(
            target, per_level, COUNTS, configs[ci].cores,
            mode="throughput",
        )["t_pred_s"]
        # traced scalars ride as f32 0-d arrays; ~1e-7 rel agreement
        assert res.t_pred_s[ci] == pytest.approx(host, rel=1e-5)


@pytest.mark.parametrize("n", [5, 8])
@pytest.mark.parametrize("with_runtime", [True, False])
@pytest.mark.parametrize("mode", ["throughput", "latency"])
def test_packed_dispatch_bit_identical_to_unpacked_inputs(
        sweep_setup, mode, with_runtime, n):
    """One dispatch's inputs travel packed in one array: hit rates stay
    bit-identical to `batched_hit_rates`, and runtimes to the ECM chain
    fed the unpacked arrays and Python-float scalars (5 configs pad to
    a chunk of 8 rows)."""
    source, session, _evaluator = sweep_setup
    ev = FusedSweepEvaluator(
        source, SPACE, session=session, mode=mode,
        counts=COUNTS if with_runtime else None,
    )
    configs = [c for c in SPACE.configs() if c.cores == 1][:n]
    assert len(configs) == n
    res = ev.evaluate(configs)
    assert ev.stats.fused_dispatches == 1

    items = oracle_items(session, source, ev, configs)
    oracle = batched_hit_rates(items)
    names = [lvl.name for lvl in ev.base.levels]
    want = [[per_level[name] for name in names] for per_level in oracle]
    assert res.rates.tolist() == want
    if not with_runtime:
        assert res.t_pred_s is None
        return

    geom = ev._geometry(configs, configs[0].line_size, 1)
    pad = batched._pow2(n) - n

    def rows(a, value):
        width = ((0, pad),) + ((0, 0),) * (a.ndim - 1)
        return np.pad(a, width, constant_values=value)

    chain = jax.jit(batched._chain_body, static_argnames=("shared_idx",
                                                          "mode"))
    t = chain(
        rows(res.rates.astype(np.float32), 1.0),
        rows(geom.trans_beta, 0.0), rows(geom.delta, 0.0),
        rows(geom.cores, 1.0),
        float(t_comp_cy(ev.timings, COUNTS, mode)),
        float(t_lsu_cy(ev.timings, COUNTS)), float(COUNTS.mem_ops),
        ev.base.ram_latency_cy, ev.base.cycle_s,
        shared_idx=ev.shared_idx, mode=mode,
    )
    assert res.t_pred_s.tolist() == np.asarray(t, np.float64)[:n].tolist()
    model = ECMRuntimeModel()
    for ci, ((target, _art), per_level) in enumerate(zip(items, oracle)):
        host = model.runtime(target, per_level, COUNTS, 1,
                             mode=mode)["t_pred_s"]
        assert res.t_pred_s[ci] == pytest.approx(host, rel=1e-5)


def test_one_packed_put_per_sweep_dispatch(sweep_setup):
    _source, _session, evaluator = sweep_setup
    configs = SPACE.configs()
    evaluator.evaluate(configs)  # warm
    telemetry.reset()
    try:
        with telemetry.enable():
            evaluator.evaluate(configs)
        snap = telemetry.snapshot()
    finally:
        telemetry.reset()
    n_levels = len(evaluator.base.levels)
    want = 0
    for cores in {c.cores for c in configs}:
        group = [c for c in configs if c.cores == cores]
        geom = evaluator._geometry(group, group[0].line_size, cores)
        keys = Counter(map(batched._sweep_akey, geom.assoc, geom.blocks))
        want += sum(map(batched._pow2, keys.values())) * (
            4 * n_levels + 6) * 4
    assert snap["sdcm.put"]["count"] == snap["sdcm.dispatch"]["count"]
    assert snap["sdcm.put"]["n"] == want


def test_pallas_inner_matches_vmap_inner(sweep_setup):
    source, session, evaluator = sweep_setup
    configs = SPACE.configs()[:6]
    vmap_res = evaluator.evaluate(configs)
    pallas_eval = FusedSweepEvaluator(
        source, SPACE, session=session, counts=COUNTS, inner="pallas",
    )
    pallas_res = pallas_eval.evaluate(configs)
    assert np.max(np.abs(pallas_res.rates - vmap_res.rates)) <= 1e-6
    assert pallas_res.t_pred_s == pytest.approx(
        vmap_res.t_pred_s, rel=1e-5
    )


def test_llc_miss_objective_without_counts(sweep_setup):
    source, session, _evaluator = sweep_setup
    ev = FusedSweepEvaluator(source, SPACE, session=session,
                             objective="llc_miss")
    configs = SPACE.configs()[:4]
    res = ev.evaluate(configs)
    assert res.t_pred_s is None
    assert np.allclose(res.scores, 1.0 - res.rates[:, -1])
    # a raw trace has no op counts: runtime objective must refuse
    with pytest.raises(ValueError, match="op counts"):
        FusedSweepEvaluator(source, SPACE, session=session,
                            objective="runtime")


def test_repeat_sweeps_compile_nothing_new(sweep_setup):
    source, session, evaluator = sweep_setup
    configs = SPACE.configs()
    evaluator.evaluate(configs)  # warm the compile caches
    before = compile_count()
    res = evaluator.evaluate(configs)
    assert compile_count() == before
    assert res.dispatches if hasattr(res, "dispatches") else True
    # profile packs are cached per (line, cores, strategy) group
    groups = {(c.line_size, c.cores, c.strategy) for c in configs}
    assert evaluator.stats.profile_groups == len(groups)


def test_sweep_geometry_matches_applied_targets(sweep_setup):
    """The staged geometry IS the applied target's geometry — the
    invariant the bit-identity test rests on."""
    _source, _session, evaluator = sweep_setup
    base = resolve_target(SPACE.target)
    li = evaluator.level_idx
    cfgs = [c for c in SPACE.configs() if c.cores == 1][:4]
    geom = evaluator._geometry(cfgs, 64, 1)
    for ci, cfg in enumerate(cfgs):
        tgt = cfg.apply(base, li)
        for lv, lvl in enumerate(tgt.levels):
            assert geom.assoc[ci, lv] == lvl.effective_assoc
            assert geom.blocks[ci, lv] == lvl.num_lines
    assert shared_level_index(base) == evaluator.shared_idx
