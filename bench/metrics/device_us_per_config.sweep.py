"""Device busy time per config scored (us): the SDCM grid and the
runtime chain on the device, from the profiler trace."""


def read(ctx):
    t, n = ctx.device_trace, ctx.records.get("configs")
    if not t or not n:
        return None
    return t["busy_s"] * 1e6 / n
