"""SDCM result reads per sweep call: the program's ``sdcm.fetch`` spans
over its ``explore.evaluate`` spans in a ``--trace 1`` window (one per
dispatch where each chunk is read before the next is sent, one per call
where every dispatch is issued first)."""
from bench.program_spans import recorded


def read(ctx):
    spans = recorded(ctx)
    if not spans or not {"explore.evaluate", "sdcm.fetch"} <= set(spans):
        return None
    return spans["sdcm.fetch"]["count"] / spans["explore.evaluate"]["count"]
