"""A sweep issues every dispatch before it reads any result.

`FusedSweepEvaluator.evaluate` issues each (line, cores, strategy)
group's chunks with `batched.issue_sweep` and reads them all in one
`batched.collect_sweeps`; `sweep_grid` is the two in a row.  The
results are the bits the read-as-you-go loop gave, and the spans show
one fetch per call, after the last program was sent.
"""
from __future__ import annotations

import glob

import numpy as np
import pytest

import jax

from repro import telemetry
from repro.api import Session, batched
from repro.api.batched import batched_hit_rates
from repro.core.incore import t_comp_cy, t_lsu_cy
from repro.core.runtime_model import OpCounts
from repro.core.trace.types import trace_from_blocks
from repro.explore import FusedSweepEvaluator, SearchSpace

COUNTS = OpCounts(int_ops=3000, fp_ops=1500, div_ops=10, loads=3000,
                  stores=1500, total_bytes=4500 * 8)

# 24 configs in two core groups, each split by its ways into the A_MAX
# buckets 8 (ways 4, 8) and 32 (ways 20)
SPACE = SearchSpace(
    sets=(512, 4096), ways=(4, 8, 20), latency_cy=(20.0, 36.0),
    cores=(1, 2), strategies=("round_robin",),
)
ROWS_PER_CHUNK = 2


def small_trace(iters=600, stride=8):
    blocks = [("OUT__1__.entry", np.array([0, 8]), True)]
    a0, b0 = 1 << 20, 2 << 20
    for i in range(iters):
        blocks.append((
            "OUT__1__.for.body",
            np.array([a0 + stride * i, b0 + stride * (i % 64), 0]),
            np.array([False, False, True]),
        ))
    return trace_from_blocks(blocks)


@pytest.fixture(scope="module")
def setup():
    source = small_trace()
    session = Session(cache_model="batched")
    ev = FusedSweepEvaluator(source, SPACE, session=session, counts=COUNTS)
    configs = SPACE.configs()
    ev.evaluate(configs)                       # packs the profiles
    max_m = max(max(p.m for p in pack) for pack in ev._packs.values())
    return source, session, ev, configs, max_m


@pytest.fixture
def small_chunks(setup, monkeypatch):
    """A cap of two rows per dispatch: one evaluate makes 12 dispatches
    over both core groups."""
    *_rest, max_m = setup
    monkeypatch.setattr(batched, "SWEEP_MAX_ELEMS",
                        2 * ROWS_PER_CHUNK * max_m)


@pytest.fixture(autouse=True)
def clean_recorder():
    telemetry.reset()
    yield
    telemetry.reset()


def _group_args(ev, configs, cores):
    idxs = [i for i, c in enumerate(configs) if c.cores == cores]
    group = [configs[i] for i in idxs]
    line, strategy = group[0].line_size, group[0].strategy
    prd, crd = ev._pack(line, cores, strategy)
    return idxs, prd, crd, ev._geometry(group, line, cores)


def _sweep_kwargs(ev, with_runtime=True):
    return dict(shared_idx=ev.shared_idx,
                counts=ev.counts if with_runtime else None,
                timings=ev.timings, cycle_s=ev.base.cycle_s,
                ram_delta=ev.base.ram_latency_cy, mode=ev.mode)


def test_evaluate_equals_group_sweeps_and_batched_hit_rates(setup,
                                                           small_chunks):
    source, session, ev, configs, _m = setup
    before = ev.stats.fused_dispatches
    res = ev.evaluate(configs)
    assert ev.stats.fused_dispatches - before >= 8

    rates = np.zeros_like(res.rates)
    t_pred = np.zeros_like(res.t_pred_s)
    for cores in (1, 2):
        idxs, prd, crd, geom = _group_args(ev, configs, cores)
        group = batched.sweep_grid(prd, crd, geom, **_sweep_kwargs(ev))
        assert group.dispatches >= 4
        rates[idxs] = group.rates
        t_pred[idxs] = group.t_pred_s
    assert res.rates.tolist() == rates.tolist()
    assert res.t_pred_s.tolist() == t_pred.tolist()
    assert res.scores.tolist() == t_pred.tolist()

    items = [(cfg.apply(ev.base, ev.level_idx),
              session.artifacts(source, cfg.cores, strategy=cfg.strategy,
                                line_size=cfg.line_size))
             for cfg in configs]
    names = [lvl.name for lvl in ev.base.levels]
    oracle = batched_hit_rates(items)
    assert res.rates.tolist() == [[r[n] for n in names] for r in oracle]


def test_one_fetch_per_evaluate_call(setup, small_chunks):
    *_rest, ev, configs, _m = setup
    before = ev.stats.fused_dispatches
    with telemetry.enable():
        ev.evaluate(configs)
    snap = telemetry.snapshot()
    dispatches = ev.stats.fused_dispatches - before
    assert dispatches >= 8
    assert snap["explore.geometry"]["count"] == 2          # cores 1, 2
    assert snap["sdcm.fetch"]["count"] == 1
    assert snap["sdcm.fetch"]["n"] == len(configs)
    for name in ("sdcm.dispatch", "sdcm.put", "sdcm.eval"):
        assert snap[name]["count"] == dispatches, name


def test_every_program_is_sent_before_the_fetch(setup, small_chunks,
                                                tmp_path):
    from jax.profiler import ProfileData

    *_rest, ev, configs, _m = setup
    before = ev.stats.fused_dispatches
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        ev.evaluate(configs)
    finally:
        jax.profiler.stop_trace()

    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    events: dict[str, list] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                events.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns))
    evals = events["sdcm.eval"]
    assert len(evals) == ev.stats.fused_dispatches - before
    (fetch,) = events["sdcm.fetch"]
    assert max(end for _start, end in evals) <= fetch[0]
    last_sweep = max(end for _start, end in events["sdcm.sweep"])
    (call,) = events["explore.evaluate"]
    assert last_sweep <= fetch[0] and fetch[1] <= call[1]


def _read_as_you_go(prd, crd, geom, *, shared_idx, counts, timings,
                    cycle_s, ram_delta, mode):
    """The sweep as it ran before: each chunk read before the next is
    sent.  Returns (rates, t_pred, dispatches, compile signatures)."""
    with_runtime = counts is not None
    if with_runtime:
        scalars = (float(t_comp_cy(timings, counts, mode)),
                   float(t_lsu_cy(timings, counts)),
                   float(counts.mem_ops), ram_delta, cycle_s)
    else:
        scalars = (0.0, 0.0, 0.0, ram_delta, cycle_s)
    c, n_levels = geom.assoc.shape
    rates = np.zeros((c, n_levels))
    t_pred = np.zeros(c) if with_runtime else None
    groups: dict[tuple, list[int]] = {}
    for ci in range(c):
        groups.setdefault(batched._sweep_akey(geom.assoc[ci],
                                              geom.blocks[ci]), []
                          ).append(ci)
    cap = max(1, batched._pow2(batched.SWEEP_MAX_ELEMS
                               // max(prd.m, crd.m)) // 2)
    sigs, dispatches = set(), 0
    for a_key, idx_list in groups.items():
        fn = batched._sweep_fn(a_key, shared_idx, mode, with_runtime)
        for lo in range(0, len(idx_list), cap):
            idxs = np.asarray(idx_list[lo:lo + cap])
            n, g = len(idxs), batched._pow2(len(idxs))
            sigs.add(("sweep", a_key, shared_idx, mode, with_runtime, g,
                      prd.m, crd.m))
            packed = jax.device_put(
                batched._pack_sweep_inputs(geom, idxs, g, scalars))
            out = fn(prd.d, prd.p, crd.d, crd.p, packed)
            dispatches += 1
            if with_runtime:
                out, t = out
                t_pred[idxs] = np.asarray(t, dtype=np.float64)[:n]
            rates[idxs] = np.asarray(out, dtype=np.float64)[:n]
    return rates, t_pred, dispatches, sigs


@pytest.mark.parametrize("with_runtime", [True, False],
                         ids=["runtime", "rates_only"])
@pytest.mark.parametrize("chunks", ["one_chunk", "many_chunks"])
def test_sweep_grid_result_is_what_reading_as_it_went_gave(
        setup, monkeypatch, chunks, with_runtime):
    *_rest, ev, configs, max_m = setup
    # one A_MAX bucket and one core group: a single chunk at the real cap
    group = [c for c in configs if c.cores == 2 and c.ways != 20]
    prd, crd = ev._pack(group[0].line_size, 2, group[0].strategy)
    assert prd.total > 0 and crd.total > 0
    geom = ev._geometry(group, group[0].line_size, 2)
    if chunks == "many_chunks":
        monkeypatch.setattr(batched, "SWEEP_MAX_ELEMS",
                            2 * ROWS_PER_CHUNK * max_m)
    kwargs = _sweep_kwargs(ev, with_runtime)
    rates, t_pred, dispatches, sigs = _read_as_you_go(
        prd, crd, geom, **kwargs)
    assert (dispatches == 1) == (chunks == "one_chunk")
    new = len(sigs - batched.compiled_signatures())

    res = batched.sweep_grid(prd, crd, geom, **kwargs)
    assert isinstance(res, batched.SweepResult)
    assert res.rates.tolist() == rates.tolist()
    if with_runtime:
        assert res.t_pred_s.tolist() == t_pred.tolist()
    else:
        assert res.t_pred_s is None
    assert res.dispatches == dispatches
    assert res.compiles == new
    assert batched.sweep_grid(prd, crd, geom, **kwargs).compiles == 0
