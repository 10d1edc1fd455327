"""Jitted SDCM dispatches per sweep call: the program's ``sdcm.dispatch``
spans over its ``explore.evaluate`` spans in a ``--trace 1`` window."""
from bench.program_spans import recorded


def read(ctx):
    spans = recorded(ctx)
    if not spans or not {"explore.evaluate", "sdcm.dispatch"} <= set(spans):
        return None
    return spans["sdcm.dispatch"]["count"] / spans["explore.evaluate"]["count"]
