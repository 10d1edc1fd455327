"""The span recorder (``repro.telemetry``) and the spans the sweep and
cold-build paths record at their layer boundaries."""
from __future__ import annotations

import glob
import threading

import numpy as np
import pytest

import jax

from repro import telemetry
from repro.api import AnalyticalSDCM, PredictionRequest, Session, batched
from repro.core.runtime_model import OpCounts
from repro.core.trace.types import trace_from_blocks
from repro.explore import FusedSweepEvaluator, SearchSpace
from repro.workloads import registry

COUNTS = OpCounts(int_ops=3000, fp_ops=1500, div_ops=10, loads=3000,
                  stores=1500, total_bytes=4500 * 8)

SPACE = SearchSpace(
    sets=(512, 4096), ways=(4, 8, 20), latency_cy=(20.0, 36.0),
    cores=(1, 2), strategies=("round_robin",),
)


@pytest.fixture(autouse=True)
def clean_recorder():
    telemetry.reset()
    yield
    telemetry.reset()
    assert not telemetry.RECORDER._enabled


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


def test_off_records_nothing():
    assert not telemetry.RECORDER._capturing()
    first = telemetry.span("x", n=1)
    for _ in range(10_000):
        with telemetry.span("x", n=1) as span:
            span.count(1)
    assert telemetry.span("y") is first
    assert telemetry.snapshot() == {}


class ThreadClock:
    """A clock each thread sets by hand."""

    def __init__(self):
        self._local = threading.local()

    def set(self, t: float) -> None:
        self._local.t = t

    def __call__(self) -> float:
        return self._local.t


def test_nesting_and_self_time_are_exact_under_a_fake_clock():
    clock = ThreadClock()
    rec = telemetry.Recorder(clock=clock, capturing=lambda: False)
    assert rec.span("x") is telemetry.span("x")      # off: the no-op
    opened, closed = threading.Event(), threading.Event()

    def other_thread():
        opened.wait(5)
        clock.set(100.0)
        with rec.span("inner", n=7):                  # a root here
            clock.set(150.0)
        closed.set()

    with rec.enable():
        worker = threading.Thread(target=other_thread)
        worker.start()
        clock.set(0.0)
        with rec.span("outer", n=2):
            clock.set(1.0)
            with rec.span("inner", n=3) as span:
                clock.set(4.0)
                span.count(2)
            clock.set(5.0)
            with rec.span("inner"):
                clock.set(6.0)
            opened.set()
            assert closed.wait(5)
            clock.set(10.0)
        worker.join(5)
    assert not worker.is_alive()
    snap = rec.snapshot()
    assert snap["outer"] == {"count": 1, "total_s": 10.0, "self_s": 6.0,
                             "n": 2}
    assert snap["inner"] == {"count": 3, "total_s": 54.0, "self_s": 54.0,
                             "n": 12}
    rec.reset()
    assert rec.snapshot() == {}
    assert rec.span("x") is telemetry.span("x")      # enable() closed


def _evaluator():
    return FusedSweepEvaluator(small_trace(), SPACE, counts=COUNTS)


def test_sweep_records_the_layer_spans_with_their_configs():
    ev = _evaluator()
    configs = SPACE.configs()
    ev.evaluate(configs)                  # warm: packs the profiles
    with telemetry.enable():
        ev.evaluate(configs)
    snap = telemetry.snapshot()
    c = len(configs)
    assert snap["explore.evaluate"]["count"] == 1
    for name in ("explore.evaluate", "explore.geometry", "sdcm.sweep",
                 "sdcm.dispatch", "sdcm.fetch"):
        assert snap[name]["n"] == c, name
    assert snap["explore.geometry"]["count"] == 2          # cores 1, 2
    assert snap["sdcm.dispatch"]["count"] == ev.stats.fused_dispatches // 2
    assert snap["sdcm.fetch"]["count"] == snap["sdcm.dispatch"]["count"]
    assert snap["sdcm.put"]["count"] == snap["sdcm.dispatch"]["count"]
    evaluate = snap["explore.evaluate"]
    assert 0 <= evaluate["self_s"] < evaluate["total_s"]
    assert snap["sdcm.eval"]["count"] == snap["sdcm.dispatch"]["count"]
    assert set(snap) == {"explore.evaluate", "explore.geometry",
                         "sdcm.sweep", "sdcm.dispatch", "sdcm.put",
                         "sdcm.eval", "sdcm.fetch"}


def test_cold_build_records_the_layer_spans_with_their_refs():
    source = registry.resolve("polybench/atx", "smoke")
    session = Session(cache_model=AnalyticalSDCM(backend="batched"))
    request = PredictionRequest(targets=("i7-5960X", "EPYC 7702P"),
                                core_counts=(1, 2),
                                counts=source.op_counts)
    with telemetry.enable():
        _tid, trace = session.load(source)
        arts = {c: session.artifacts(source, c) for c in (1, 2)}
        session.predict(source, request)
    snap = telemetry.snapshot()
    refs = len(trace)
    privs, shared = arts[2].privates, arts[2].shared
    assert snap["workload.trace"] == dict(snap["workload.trace"], count=1,
                                          n=refs)
    assert snap["reuse.mimic"]["n"] == refs
    assert snap["reuse.interleave"]["n"] == sum(len(p) for p in privs)
    scanned = refs + len(privs[0]) + len(shared)
    assert snap["reuse.distance"] == dict(snap["reuse.distance"], count=3,
                                          n=scanned)
    assert snap["reuse.histogram"]["n"] == scanned
    cells = len(list(request.cells()))
    assert snap["session.predict"]["n"] == cells
    assert snap["runtime.model"]["n"] == cells
    assert snap["sdcm.grid"]["n"] == sum(
        len(cell.target.levels) for cell in request.cells())
    assert snap["sdcm.dispatch"]["n"] == snap["sdcm.grid"]["n"]
    # two builds, then one artifact lookup per cell inside predict
    assert snap["session.artifacts"]["count"] == 2 + cells
    art = snap["session.artifacts"]
    children = sum(snap[n]["total_s"] for n in (
        "reuse.mimic", "reuse.interleave", "reuse.distance",
        "reuse.histogram"))
    assert art["self_s"] == pytest.approx(art["total_s"] - children,
                                          abs=1e-9)


def test_profiler_capture_holds_the_spans_with_their_stats(tmp_path):
    from jax.profiler import ProfileData

    ev = _evaluator()
    configs = SPACE.configs()
    ev.evaluate(configs)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        ev.evaluate(configs)              # records: a capture is active
    finally:
        jax.profiler.stop_trace()
    assert telemetry.snapshot()["explore.evaluate"]["n"] == len(configs)

    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    events: dict[str, list] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                events.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats)))
    (call,) = events["explore.evaluate"]
    assert call[2]["n"] == len(configs)
    dispatches = events["sdcm.dispatch"]
    assert sum(d[2]["n"] for d in dispatches) == len(configs)
    assert all(call[0] <= s and e <= call[1] for s, e, _ in dispatches)
    assert {"explore.geometry", "sdcm.sweep", "sdcm.fetch"} <= set(events)


def test_jitted_programs_carry_stable_names():
    """A profiler names a device op by its program: ``jit_<name>/...``."""
    f32 = np.float32
    d = jax.ShapeDtypeStruct((2, 8), f32)
    row = jax.ShapeDtypeStruct((2,), f32)
    grid = batched._grid_fn(8).lower(d, d, row, row)
    fold = batched.sdcm_fold.lower(jax.ShapeDtypeStruct((8,), f32),
                                   jax.ShapeDtypeStruct((8,), f32))
    cl = jax.ShapeDtypeStruct((2, 3), f32)
    m = jax.ShapeDtypeStruct((8,), f32)
    packed = jax.ShapeDtypeStruct((2, 4 * 3 + 6), f32)
    scalars = (1.0,) * 5
    sweep = batched._sweep_fn((8, 8, 8), 2, "throughput", True).lower(
        m, m, m, m, packed)
    chain = batched._chain_fn(3, 2, "throughput").lower(
        cl, cl, cl, row, *scalars)
    for lowered, name in ((grid, "sdcm_grid"), (fold, "sdcm_fold"),
                          (sweep, "sdcm_sweep"), (chain, "ecm_chain")):
        assert f"module @jit_{name}" in lowered.as_text()
