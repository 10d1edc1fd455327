"""Trace generation per reference (us): the self time of the program's
``workload.trace`` spans (each place a ``Session`` builds a trace) over
the references they built, in a ``--trace 1`` window."""
from bench.program_spans import self_us_per_unit


def read(ctx):
    return self_us_per_unit(ctx, "workload.trace")
