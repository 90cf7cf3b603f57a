"""Arithmetic shared by the per-layer metric readers in ``metrics/``.

A reader gets the traced run's context: ``call`` ("run" or "sweep"),
``units`` (policy-weeks or cells completed in the window), ``window_s``
(its host seconds), ``phases`` (the program's phase profiler seconds over
the window), ``device`` (the trace reduction of one more request, or
None) and ``traced_units``.
Each returns None when its cell has nothing to read."""
from __future__ import annotations

SCAN_PROGRAMS = ("jit__single_chunk", "jit__single_chunk_batch")


def phase_ms(ctx: dict, phase: str, call: str) -> float | None:
    """Milliseconds of a program phase per unit of the window."""
    if ctx["call"] != call or not ctx["units"] or phase not in ctx["phases"]:
        return None
    return 1000.0 * ctx["phases"][phase] / ctx["units"]


def unprofiled_ms(ctx: dict, call: str) -> float | None:
    """Milliseconds per unit of the window that no program phase
    brackets: host work between the phases."""
    if ctx["call"] != call or not ctx["units"] or not ctx["phases"]:
        return None
    rest = ctx["window_s"] - sum(ctx["phases"].values())
    return 1000.0 * max(rest, 0.0) / ctx["units"]


def scan_device_ms(ctx: dict, call: str) -> float | None:
    """Device milliseconds of the scan programs per traced unit."""
    dev = ctx["device"]
    if ctx["call"] != call or dev is None:
        return None
    secs = [s for name, s in dev["program_s"].items()
            if name in SCAN_PROGRAMS]
    if not secs:
        return None
    return 1000.0 * sum(secs) / ctx["traced_units"]


def device_idle(ctx: dict, call: str) -> float | None:
    """1 - device-busy share of the traced request."""
    dev = ctx["device"]
    if ctx["call"] != call or dev is None or dev["window_s"] <= 0:
        return None
    return 1.0 - dev["busy_s"] / dev["window_s"]
