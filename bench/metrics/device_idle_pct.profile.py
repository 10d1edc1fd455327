"""Share of the window in which no operation ran on the device (%),
from the profiler trace of a ``--trace 1`` run."""
from bench.metrics import device_idle_pct


def read(ctx):
    return device_idle_pct(ctx)
