"""Interleaving (Algorithm 2) per reference (us): the self time of the
program's ``reuse.interleave`` spans over the private-trace references
they merged, in a ``--trace 1`` window."""
from bench.program_spans import self_us_per_unit


def read(ctx):
    return self_us_per_unit(ctx, "reuse.interleave")
