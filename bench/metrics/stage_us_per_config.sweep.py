"""Host staging per config scored (us): the self time of the program's
``explore.evaluate``, ``explore.geometry`` and ``sdcm.sweep`` spans
(grouping, geometry arrays, bucket keys, chunking), over the configs
``explore.evaluate`` scored in a ``--trace 1`` window."""
from bench.program_spans import us_per_config

STAGES = ("explore.evaluate", "explore.geometry", "sdcm.sweep")


def read(ctx):
    return us_per_config(ctx, STAGES, "self_s")
