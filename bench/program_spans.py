"""What the program's own spans (``repro.telemetry``) recorded, for the
per-layer metrics that read them.

The program records its spans only while a profiler capture is active
(or while ``telemetry.enable()`` is in force, which the harness never
uses), so in a ``--trace 1`` run the aggregates cover exactly the
window.  An untraced run, a program without ``repro.telemetry`` and a
span that was never recorded all read ``None``.
"""
from __future__ import annotations


def recorded(ctx) -> dict | None:
    """name -> {count, total_s, self_s, n}, or None."""
    if not ctx.trace:
        return None
    try:
        from repro import telemetry
    except ImportError:
        return None
    return telemetry.snapshot() or None


def self_us_per_unit(ctx, name: str) -> float | None:
    """Self time of span ``name`` per unit of its ``n`` (us)."""
    spans = recorded(ctx)
    s = spans.get(name) if spans else None
    if not s or not s["n"]:
        return None
    return s["self_s"] * 1e6 / s["n"]


def us_per_config(ctx, names, key: str) -> float | None:
    """``key`` (``total_s`` or ``self_s``) summed over the spans
    ``names``, per config that ``explore.evaluate`` scored (us)."""
    spans = recorded(ctx)
    calls = spans.get("explore.evaluate") if spans else None
    found = [spans[n][key] for n in names if n in spans] if calls else []
    if not found or not calls["n"]:
        return None
    return sum(found) * 1e6 / calls["n"]
