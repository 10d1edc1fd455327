"""Time in reuse distance, mimicry, interleaving and profiles per trace
reference (us): the harness's ``artifacts`` spans around
``Session.artifacts`` for every core count."""


def read(ctx):
    refs = ctx.records.get("refs")
    if not refs:
        return None
    return ctx.span_seconds("artifacts") * 1e6 / refs
