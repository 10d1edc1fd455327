"""SDCM dispatch per config scored (us): the program's ``sdcm.dispatch``
spans (padding, host-to-device transfers, the jitted call), over the
configs ``explore.evaluate`` scored in a ``--trace 1`` window."""
from bench.program_spans import us_per_config


def read(ctx):
    return us_per_config(ctx, ("sdcm.dispatch",), "total_s")
