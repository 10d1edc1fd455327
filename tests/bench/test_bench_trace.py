"""The trace reduction: busy time is the union of device operations in
the window, idle gaps are labelled by the harness's spans."""
from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

from bench import trace

from bench_cells import ROOT

RECORDED = ROOT / "tests" / "bench" / "data" / "probe.xplane.pb"


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _profile(device_events, host_events, devices=1):
    planes = [NS(name="/host:CPU", lines=[NS(name="python",
                                             events=host_events)])]
    for i in range(devices):
        planes.append(NS(name=f"/device:TPU:{i}", lines=[
            NS(name="XLA Modules", events=[_ev("jit_run(123)", 0, 10**9)]),
            NS(name="XLA Ops", events=device_events if i == 0 else []),
        ]))
    return NS(planes=planes)


def test_busy_is_the_union_of_ops_inside_the_window():
    ops = [_ev("fusion", 100, 50), _ev("fusion", 120, 60),   # 100..180
           _ev("reduce", 300, 100),                           # 300..400
           _ev("copy", 950, 200)]                             # clipped
    host = [_ev("bench.window", 50, 950),                     # 50..1000
            _ev("bench.sweep", 60, 400), _ev("bench.prep", 180, 100),
            _ev("bench.check", 1200, 10)]
    out = trace.reduce_profile(_profile(ops, host), chips=1)
    assert out["window_s"] == pytest.approx(950e-9)
    assert out["busy_s"] == pytest.approx((80 + 100 + 50) * 1e-9)
    ops_time = dict(out["breakdown"]["device_ops"])
    assert ops_time == pytest.approx({"jit_run/fusion": 110e-9,
                                      "jit_run/reduce": 100e-9,
                                      "jit_run/copy": 50e-9})
    gaps = dict(out["breakdown"]["idle_gaps"])
    # 50..100 and 180..300 under bench.sweep (the 180..280 prep span is
    # inner but the gap's middle, 240, lies in it), 400..950 outside
    assert gaps == pytest.approx({"bench.sweep": 50e-9,
                                  "bench.prep": 120e-9,
                                  "host.other": 550e-9})


def test_busy_is_averaged_over_the_chips_used():
    ops = [_ev("fusion", 0, 100)]
    host = [_ev("bench.window", 0, 200)]
    one = trace.reduce_profile(_profile(ops, host, devices=2), chips=1)
    two = trace.reduce_profile(_profile(ops, host, devices=2), chips=2)
    assert one["busy_s"] == pytest.approx(100e-9)
    assert two["busy_s"] == pytest.approx(50e-9)


def test_no_device_operation_reads_nothing():
    prof = NS(planes=[NS(name="/host:CPU", lines=[])])
    assert trace.reduce_profile(prof, chips=1) is None
    assert trace.reduce_dir(str(ROOT / "bench" / "configs"), 1) is None


def test_recorded_chip_trace():
    """A trace recorded on one TPU v5e: two jitted programs and a host
    sleep inside ``bench.window``, three times."""
    out = trace.reduce_file(str(RECORDED), chips=1)
    assert 0 < out["busy_s"] < out["window_s"]
    names = [n for n, _s in out["breakdown"]["device_ops"]]
    assert names and len(names) <= trace.TOP
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert "bench.host" in gaps
    assert gaps["bench.host"] == max(gaps.values())
