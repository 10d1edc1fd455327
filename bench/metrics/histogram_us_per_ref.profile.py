"""Profiles per reference (us): the self time of the program's
``reuse.histogram`` spans (reuse distances to a reuse profile) over the
distances they counted, in a ``--trace 1`` window."""
from bench.program_spans import self_us_per_unit


def read(ctx):
    return self_us_per_unit(ctx, "reuse.histogram")
