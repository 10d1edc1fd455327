"""The benchmark harness: one cell, one run, one result line.

Everything specific lives in files found by name:

* ``BENCHMARK.json`` names each cell's configuration and traffic mix;
* ``bench/configs/<config>.json`` holds the deployment as it is run;
* ``bench/traffic/<traffic>.json`` holds the mix's parameters and the
  name of the general driver that reads them;
* ``bench/drivers/<driver>.py`` drives one kind of traffic: ``setup``,
  ``window`` and ``check`` (the comparison with ``bench/reference``);
* ``bench/metrics/<metric>.py`` reads one metric from a finished run,
  or returns ``None`` where it finds nothing to read.

A new cell, mix or metric is a new file; no file here changes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_module(path: Path):
    """Import one file by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path
    )
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    bench_dir: Path = BENCH_DIR

    def driver(self):
        return load_module(
            self.bench_dir / "drivers" / f"{self.traffic['driver']}.py")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        known = [w["name"] for w in spec["workloads"]]
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")
    bench = root / "bench"
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=load_json(bench / "configs" / f"{entry['config']}.json"),
        traffic=load_json(bench / "traffic" / f"{entry['traffic']}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        bench_dir=bench,
    )


# --- spans and counters ----------------------------------------------------


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float


class RunContext:
    """What a driver records while it runs: host spans (also written
    into the profiler's trace as ``bench.<name>``), counters, and the
    driver's own records for the metric readers."""

    def __init__(self, seed: int, seconds: float, trace: bool):
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.spans: list[Span] = []
        self.counters: dict[str, float] = {}
        self.records: dict = {}
        self.device_trace: dict | None = None
        self.setup_s: float = 0.0

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            try:
                yield
            finally:
                self.spans.append(Span(name, t0, time.perf_counter()))

    def span_seconds(self, name: str) -> float:
        return sum(s.end - s.start for s in self.spans if s.name == name)

    def count(self, name: str, by: float = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + by


class CompileCounter:
    """XLA compilations seen by JAX's monitoring hooks, plus the
    program's own count of kernel signatures (``compile_count``)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.backend = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_event)

    def _on_event(self, event: str, _secs: float, **_kw) -> None:
        if event == self.EVENT:
            self.backend += 1

    def read(self) -> tuple[int, int]:
        from repro.api.batched import compile_count

        return self.backend, compile_count()


# --- device ----------------------------------------------------------------


class NoAccelerator(SystemExit):
    pass


def check_device(chips: int) -> dict:
    """The run's device: a TPU with at least ``chips`` chips, or exit."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoAccelerator(
            f"bench: this benchmark runs on a TPU; JAX found {platform!r}"
        )
    if len(devices) < chips:
        raise NoAccelerator(
            f"bench: the cell needs {chips} chips; JAX found {len(devices)}"
        )
    peaks(devices[0].device_kind)
    return device_info(devices[:chips])


def peaks(kind: str) -> dict:
    """The published peaks of one chip of ``kind`` (``bench/peaks.json``);
    a device missing from the table is an error, not a default."""
    table = load_json(BENCH_DIR / "peaks.json")["devices"]
    if kind not in table:
        raise NoAccelerator(
            f"bench: no peaks for device kind {kind!r} in bench/peaks.json"
        )
    return table[kind]


def device_info(devices) -> dict:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


# --- one run -----------------------------------------------------------------


@dataclasses.dataclass
class Check:
    """One number compared with its limit: the run is correct when
    every ``value <= limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def read_metrics(metrics: list[dict], ctx: RunContext,
                 bench_dir: Path = BENCH_DIR) -> dict:
    out = {}
    for m in metrics:
        reader = load_module(bench_dir / "metrics" / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def _profile_options():
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    return options


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             started: float, devices=None, log=print,
             control: bool = False) -> dict:
    """Set up, measure for ``seconds``, compare, and return the result
    object.  ``started`` is the process's start on the host clock.
    ``control`` adds the readings of the control (the reference in a
    lower precision, in the program's place) under ``control``."""
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = devices if devices is not None else jax.devices()[:cell.chips]
    driver = cell.driver()
    ctx = RunContext(seed, seconds, trace)
    compiles = CompileCounter()
    state = driver.setup(cell.config, cell.traffic, ctx)
    ctx.setup_s = time.perf_counter() - started
    before = compiles.read()

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace_dir:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=_profile_options())
        try:
            with ctx.span("window"):
                driver.window(state, ctx)
        finally:
            if trace_dir:
                jax.profiler.stop_trace()
        after = compiles.read()
        compiles.close()
        if trace_dir:
            from bench.trace import reduce_dir

            ctx.device_trace = reduce_dir(trace_dir, len(devices))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    peak = memory_peak_bytes(devices)
    log(f"[window] compiles in window: {after[0] - before[0]} XLA, "
        f"{after[1] - before[1]} kernel signatures")
    for line in ctx.records.get("log", []):
        log(line)
    t_check = time.perf_counter()
    checks = driver.check(state, ctx)
    log(f"[check] reference and comparison: "
        f"{time.perf_counter() - t_check:.3f} s")
    controls = driver.control(state) if control else None
    del state
    metrics = read_metrics(cell.per_layer if trace else cell.end_to_end, ctx,
                           cell.bench_dir)

    device = device_info(devices)
    device["memory_peak_bytes"] = peak
    result = {
        "correct": all(c.ok for c in checks),
        "attempted": int(ctx.records.get("attempted", 0)),
        "failed": int(ctx.records.get("failed", 0)),
        "metrics": metrics,
        "device": device,
    }
    if trace and ctx.device_trace is not None:
        device["busy_s"] = ctx.device_trace["busy_s"]
        device["window_s"] = ctx.device_trace["window_s"]
        result["breakdown"] = ctx.device_trace["breakdown"]
    if controls is not None:
        result["control"] = {c.name: {"value": c.value, "limit": c.limit}
                             for c in controls}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def main(argv=None, *, started: float | None = None) -> int:
    import argparse

    if started is None:
        started = time.perf_counter()
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    cell = load_cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        check_device(cell.chips)
    except NoAccelerator as exc:
        print(exc, file=sys.stderr)
        return 3
    import jax

    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      started=started, devices=jax.devices()[:cell.chips])
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
