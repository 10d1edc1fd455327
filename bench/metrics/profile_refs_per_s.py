"""Trace references of every cold build completed in the window, over
the time to the end of the last completed build."""


def read(ctx):
    r = ctx.records
    if "refs" not in r or r["elapsed_s"] <= 0:
        return None
    return r["refs"] / r["elapsed_s"]
