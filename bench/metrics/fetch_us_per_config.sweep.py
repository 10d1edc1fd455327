"""SDCM result wait per config scored (us): the program's ``sdcm.fetch``
spans (the host blocked on the device, then the copy back), over the
configs ``explore.evaluate`` scored in a ``--trace 1`` window."""
from bench.program_spans import us_per_config


def read(ctx):
    return us_per_config(ctx, ("sdcm.fetch",), "total_s")
