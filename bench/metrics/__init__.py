"""One reader per metric, named as the metric: ``read(ctx)`` returns the
value from a finished run, or ``None`` where the run has nothing to
read for it."""


def device_idle_pct(ctx):
    """100 x (1 - device busy / window) from the run's profiler trace."""
    t = ctx.device_trace
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
