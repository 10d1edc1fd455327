"""Configs scored (every level's hit rate and the runtime) over the
window, which ends at the end of the call that crosses ``--seconds``."""


def read(ctx):
    r = ctx.records
    if "configs" not in r or r["elapsed_s"] <= 0:
        return None
    return r["configs"] / r["elapsed_s"]
