"""Host-to-device transfers per SDCM sweep dispatch: the program's
``sdcm.put`` spans (one packed input transfer each) over its
``sdcm.dispatch`` spans in a ``--trace 1`` window; nothing on a
program that records no ``sdcm.put``."""
from bench.program_spans import recorded


def read(ctx):
    spans = recorded(ctx)
    if not spans or not {"sdcm.dispatch", "sdcm.put"} <= set(spans):
        return None
    return spans["sdcm.put"]["count"] / spans["sdcm.dispatch"]["count"]
