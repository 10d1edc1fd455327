"""Mimicry (Algorithm 1) per reference (us): the self time of the
program's ``reuse.mimic`` spans over the references of the traces they
split into private traces, in a ``--trace 1`` window."""
from bench.program_spans import self_us_per_unit


def read(ctx):
    return self_us_per_unit(ctx, "reuse.mimic")
