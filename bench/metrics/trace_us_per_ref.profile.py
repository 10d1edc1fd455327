"""Host time in trace generation per reference (us): the harness's
``load`` spans around ``Session.load``."""


def read(ctx):
    refs = ctx.records.get("refs")
    if not refs:
        return None
    return ctx.span_seconds("load") * 1e6 / refs
