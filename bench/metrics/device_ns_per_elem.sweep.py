"""Device busy time per padded row-distance element (ns): the profiler
trace's busy time over the ``n`` of every ``sdcm.eval`` span in a
``--trace 1`` window, the SDCM kernel's cost per unit of work; nothing
on a program that records no ``sdcm.eval``."""
from bench.program_spans import recorded


def read(ctx):
    t, spans = ctx.device_trace, recorded(ctx)
    s = spans.get("sdcm.eval") if spans else None
    if not t or not s or not s["n"]:
        return None
    return t["busy_s"] * 1e9 / s["n"]
