"""Reuse distance per reference (us): the self time of the program's
``reuse.distance`` spans over the references they scanned, in a
``--trace 1`` window."""
from bench.program_spans import self_us_per_unit


def read(ctx):
    return self_us_per_unit(ctx, "reuse.distance")
