"""Spans at the program's layer boundaries.

``with span("sdcm.dispatch", n=rows):`` marks one call into a layer.
A span records only while :func:`enable` is in force or while a JAX
profiler capture is active; otherwise it costs one flag test and one
call into jaxlib, and returns a shared no-op context.

While recording, each span adds to per-name aggregates:

* ``count``: spans closed;
* ``total_s``: their summed duration on ``time.perf_counter``;
* ``self_s``: that duration less the part covered by child spans of
  the same thread (spans nest per thread);
* ``n``: the units of work the spans reported (configs, rows,
  references), from ``n=`` or :meth:`Span.count`.

Memory is bounded by the number of distinct names.  While a profiler
capture is active, each span is also a ``TraceAnnotation`` on the
profiler's host plane, on the device trace's clock, with ``n`` and
any extra keyword (``method=...``) as its stats.  :func:`snapshot`
reads the aggregates; :func:`reset` clears them.
"""
from __future__ import annotations

import contextlib
import threading
import time

try:
    from jax._src.lib import _profiler
    from jax.profiler import TraceAnnotation

    _capturing = _profiler.TraceMe.is_enabled
except (ImportError, AttributeError):  # a jaxlib without TraceMe
    TraceAnnotation = None

    def _capturing() -> bool:
        return False


class _Off:
    """The span returned while nothing records: does nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *_exc):
        return False

    def count(self, n) -> None:
        pass


_OFF = _Off()


class Span:
    """One open span; ``count(n)`` adds units of work to it."""

    __slots__ = ("_rec", "name", "n", "_stats", "_t0", "_child_s", "_tm")

    def __init__(self, rec: "Recorder", name: str, n, stats: dict):
        self._rec = rec
        self.name = name
        self.n = n or 0
        self._stats = stats
        self._child_s = 0.0
        self._tm = None

    def count(self, n) -> None:
        self.n += n

    def __enter__(self):
        rec = self._rec
        if TraceAnnotation is not None and rec._capturing():
            self._tm = TraceAnnotation(self.name, **self._stats)
            self._tm.__enter__()
        stack = rec._stack()
        stack.append(self)
        self._t0 = rec._clock()
        return self

    def __exit__(self, *exc):
        rec = self._rec
        dur = rec._clock() - self._t0
        stack = rec._stack()
        stack.pop()
        if stack:
            stack[-1]._child_s += dur
        rec._add(self.name, dur, dur - self._child_s, self.n)
        if self._tm is not None:
            if self.n:
                self._tm.set_metadata(n=self.n)
            self._tm.__exit__(*exc)
        return False


class Recorder:
    """Per-name span aggregates; one per process is the default
    (:data:`RECORDER`), tests make their own."""

    def __init__(self, clock=time.perf_counter, capturing=_capturing):
        self._clock = clock
        self._capturing = capturing
        self._enabled = 0
        self._lock = threading.Lock()
        self._local = threading.local()
        self._totals: dict[str, list] = {}

    def span(self, name: str, n=None, **stats):
        """A context for one call into a layer (see the module)."""
        if not self._enabled and not self._capturing():
            return _OFF
        return Span(self, name, n, stats)

    @contextlib.contextmanager
    def enable(self):
        """Record in every thread while the context is open."""
        with self._lock:
            self._enabled += 1
        try:
            yield self
        finally:
            with self._lock:
                self._enabled -= 1

    def snapshot(self) -> dict[str, dict]:
        """name -> {count, total_s, self_s, n}, copied."""
        with self._lock:
            return {k: {"count": v[0], "total_s": v[1], "self_s": v[2],
                        "n": v[3]} for k, v in self._totals.items()}

    def reset(self) -> None:
        with self._lock:
            self._totals.clear()

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _add(self, name: str, total: float, self_s: float, n) -> None:
        with self._lock:
            agg = self._totals.get(name)
            if agg is None:
                agg = self._totals[name] = [0, 0.0, 0.0, 0]
            agg[0] += 1
            agg[1] += total
            agg[2] += self_s
            agg[3] += n


RECORDER = Recorder()
span = RECORDER.span
enable = RECORDER.enable
snapshot = RECORDER.snapshot
reset = RECORDER.reset

__all__ = ["RECORDER", "Recorder", "Span", "enable", "reset", "snapshot",
           "span"]
