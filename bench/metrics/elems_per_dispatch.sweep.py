"""Padded row-distance elements per SDCM dispatch: the ``n`` of the
program's ``sdcm.eval`` spans (each jitted call's padded rows times its
padded profile width) over their count, in a ``--trace 1`` window;
nothing on a program that records no ``sdcm.eval``."""
from bench.program_spans import recorded


def read(ctx):
    spans = recorded(ctx)
    s = spans.get("sdcm.eval") if spans else None
    if not s or not s["count"]:
        return None
    return s["n"] / s["count"]
