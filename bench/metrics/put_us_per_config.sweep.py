"""SDCM input transfer per config scored (us): the program's
``sdcm.put`` spans (one packed host-to-device transfer per dispatch,
inside ``sdcm.dispatch``), over the configs ``explore.evaluate`` scored
in a ``--trace 1`` window."""
from bench.program_spans import us_per_config


def read(ctx):
    return us_per_config(ctx, ("sdcm.put",), "total_s")
