"""Reduction of a ``jax.profiler`` trace to the benchmark's device numbers.

The trace (an ``.xplane.pb``) holds one plane per TPU (``/device:TPU:<i>``)
whose ``XLA Modules`` line has one event per program execution and whose
``XLA Ops`` line has one event per operation (a ``%while`` op's event
spans the ops of its body), and host planes whose threads carry the
benchmark's own ``TraceAnnotation`` spans (names that start with
``chipbench``).  All share one nanosecond clock.

- busy: the union of the program-execution intervals inside the window,
  averaged over the devices that ran anything;
- per-program device time: the summed durations of each program's
  executions (``jit__geo_chunk(<hash>)`` counts as ``jit__geo_chunk``);
- device ops: each operation's summed time in the window, named
  ``<program>/<op>`` after the program execution it ran in;
- idle: the stretches of the window with no program running, cut where a
  benchmark span starts or ends, each piece labelled with the innermost
  span around it and the program that ran next, and summed by label.
"""
from __future__ import annotations

import bisect
import re

ANNOTATION_PREFIX = "chipbench"
_HASH = re.compile(r"\(\d+\)$")


def module_name(name: str) -> str:
    """``jit__single_chunk(123)`` -> ``jit__single_chunk``."""
    return _HASH.sub("", name)


def op_name(name: str) -> str:
    """An HLO op event's short name: ``%fusion.7 = f32[..] ...`` ->
    ``%fusion.7``."""
    return name.split(" = ", 1)[0].strip()


def extract(path: str) -> dict:
    """Read the parts of an ``.xplane.pb`` the reduction needs into plain
    lists: per device, (start_ns, end_ns, name) of program executions and
    of operations; the benchmark's host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: dict[str, dict] = {}
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = devices.setdefault(plane.name, {"modules": [], "ops": []})
            for line in plane.lines:
                if line.name == "XLA Modules":
                    key, name = "modules", module_name
                elif line.name == "XLA Ops":
                    key, name = "ops", op_name
                else:
                    continue
                for ev in line.events:
                    s = float(ev.start_ns)
                    dev[key].append((s, s + float(ev.duration_ns),
                                     name(ev.name)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(ANNOTATION_PREFIX):
                        s = float(ev.start_ns)
                        spans.append((s, s + float(ev.duration_ns), ev.name))
    return {"devices": {k: {"modules": sorted(v["modules"]),
                            "ops": sorted(v["ops"])}
                        for k, v in devices.items()},
            "spans": sorted(spans)}


def _union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals clipped to [lo, hi]."""
    out: list[list[float]] = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _ops_by_program(mods, ops, lo: float, hi: float) -> dict[str, float]:
    """Nanoseconds of each ``<program>/<op>`` inside [lo, hi]; an op
    belongs to the program execution that holds its start."""
    starts = [m[0] for m in mods]
    out: dict[str, float] = {}
    for s, e, name in ops:
        if e <= lo or s >= hi:
            continue
        i = bisect.bisect_right(starts, s) - 1
        prog = mods[i][2] if i >= 0 and mods[i][1] >= s else "?"
        key = f"{prog}/{name}"
        out[key] = out.get(key, 0.0) + min(e, hi) - max(s, lo)
    return out


def _idle_by_label(gaps, spans, starts, window_span: str) -> dict[str, float]:
    """Idle nanoseconds by ``<innermost span> | before <next program>``,
    each gap cut where a span inside the window starts or ends."""
    cuts = sorted({x for s, e, _ in spans for x in (s, e)})
    out: dict[str, float] = {}
    for g0, g1 in gaps:
        i, j = bisect.bisect_right(cuts, g0), bisect.bisect_left(cuts, g1)
        pts = [g0] + cuts[i:j] + [g1]
        k = bisect.bisect_left(starts, (g1, ""))
        nxt = starts[k][1] if k < len(starts) else "window end"
        for a, b in zip(pts, pts[1:]):
            mid = 0.5 * (a + b)
            inner = [(e - s, n) for s, e, n in spans if s <= mid <= e]
            where = min(inner)[1] if inner else window_span
            label = f"{where} | before {nxt}"
            out[label] = out.get(label, 0.0) + (b - a)
    return out


def reduce(data: dict, window_span: str) -> dict | None:
    """Device numbers over the window that the host span named
    ``window_span`` covers (its first start to its last end).

    Returns None when the trace holds no such span or no device ran
    anything in it; else seconds: ``window_s``, ``busy_s`` (mean over the
    devices that ran), ``program_s`` per program, the top device ops and
    the idle time by what the host was doing."""
    marks = [(s, e) for s, e, n in data["spans"] if n == window_span]
    if not marks:
        return None
    lo, hi = min(s for s, _ in marks), max(e for _, e in marks)
    busy_by_dev = {}
    program_ns: dict[str, float] = {}
    ops_ns: dict[str, float] = {}
    all_mods = []
    for dev, d in data["devices"].items():
        mods = [m for m in d["modules"] if m[1] > lo and m[0] < hi]
        if not mods:
            continue
        busy_by_dev[dev] = _union(mods, lo, hi)
        for s, e, name in mods:
            program_ns[name] = program_ns.get(name, 0.0) \
                + (min(e, hi) - max(s, lo))
        for k, ns in _ops_by_program(d["modules"], d["ops"], lo, hi).items():
            ops_ns[k] = ops_ns.get(k, 0.0) + ns
        all_mods.extend(mods)
    if not busy_by_dev:
        return None
    busy_ns = sum(sum(e - s for s, e in u) for u in busy_by_dev.values()) \
        / len(busy_by_dev)
    # idle on the union of all devices' busy intervals
    gaps = []
    cursor = lo
    for s, e in _union(all_mods, lo, hi) + [(hi, hi)]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    spans = [sp for sp in data["spans"]
             if sp[2] != window_span and sp[1] > lo and sp[0] < hi]
    idle = _idle_by_label(gaps, spans, sorted((m[0], m[2]) for m in all_mods),
                          window_span)
    top_ops = sorted(ops_ns.items(), key=lambda x: -x[1])[:10]
    top_idle = sorted(idle.items(), key=lambda x: -x[1])[:10]
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy_ns * 1e-9,
            "devices": len(busy_by_dev),
            "program_s": {k: v * 1e-9 for k, v in program_ns.items()},
            "device_ops": [[k, v * 1e-9] for k, v in top_ops],
            "idle_gaps": [[k, v * 1e-9] for k, v in top_idle]}
