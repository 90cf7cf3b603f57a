"""Lightweight phase profilers for the experiment pipeline.

Four canonical phases bracket where each run's wall-clock goes:

- ``learn``     — knowledge-base construction (``learn_window``);
- ``provision`` — scenario materialisation + policy construction;
- ``decide``    — policy decisions (per-slot on the host engines; the
  fused device scan on the scan path, ``block_until_ready``-bracketed);
- ``execute``   — progress/energy accounting and bookkeeping.

Every other bracket is a *span*: host work between the phases (``pack``,
``policy_tables``, ``build``, ``rows``) or a part of one, named by its
path (``decide/wait``, ``learn/oracle``).  Phases book into ``seconds``
(whose sum is :meth:`total`), spans into ``spans``, so a span never
changes what a phase reads.  ``count`` adds to named ``counters``
(``scan_slots``, ``h2d_bytes``, ...).

Timers use ``perf_counter`` and cost one branch per slot when attached;
the engines skip them entirely when no profiler is threaded
(:func:`span` is a no-op context then).  Device work is synchronised
before a bracket closes (:meth:`sync`) so scan timings measure compute,
not dispatch.  Each bracket also opens a ``jax.profiler.TraceAnnotation``
of its name, so wrapping a call in ``jax.profiler.trace(dir)`` puts the
program's phases and spans on the device timeline."""
from __future__ import annotations

import contextlib
import time

PHASES = ("learn", "provision", "decide", "execute")
_OFF = contextlib.nullcontext()


def _annotation(name: str):
    try:
        import jax
    except ImportError:              # pragma: no cover - jax is baked in
        return _OFF
    return jax.profiler.TraceAnnotation(name)


class PhaseProfiler:
    """Accumulates wall-clock seconds (and bracket counts) per phase and
    per span, and integer counters."""

    def __init__(self) -> None:
        self.seconds: dict[str, float] = {}
        self.calls: dict[str, int] = {}
        self.spans: dict[str, dict] = {}        # name -> {seconds, calls}
        self.counters: dict[str, int] = {}

    def add(self, phase: str, dt: float) -> None:
        if phase in PHASES:
            self.seconds[phase] = self.seconds.get(phase, 0.0) + dt
            self.calls[phase] = self.calls.get(phase, 0) + 1
            return
        s = self.spans.setdefault(phase, {"seconds": 0.0, "calls": 0})
        s["seconds"] += dt
        s["calls"] += 1

    def count(self, name: str, n: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + int(n)

    @contextlib.contextmanager
    def phase(self, name: str, sync=None):
        """Bracket a phase or span; ``sync`` (any jax pytree) is
        ``block_until_ready``-ed before the timer stops."""
        t = time.perf_counter()
        with _annotation(name):
            try:
                yield
            finally:
                if sync is not None:
                    self.sync(sync)
                self.add(name, time.perf_counter() - t)

    @staticmethod
    def sync(tree) -> None:
        """Block until device work in ``tree`` has finished (no-op when
        jax is unavailable or the tree holds no device arrays)."""
        try:
            import jax
        except ImportError:          # pragma: no cover - jax is baked in
            return
        jax.block_until_ready(tree)

    def total(self) -> float:
        return sum(self.seconds.values())

    def summary(self) -> dict:
        """``{"timers": {name: {seconds, calls, share}}, "counters": ...}``.
        Timers list the canonical phases first, then the spans that sit
        between phases, each followed by the spans inside it
        (``decide/wait`` under ``decide``).  ``share`` is of the
        bracketed time: the phases plus the top-level spans."""
        tops = {p: {"seconds": self.seconds[p], "calls": self.calls[p]}
                for p in PHASES if p in self.seconds}
        tops.update((s, dict(d)) for s, d in self.spans.items()
                    if "/" not in s)
        order = []
        for top in tops:
            order += [top] + [s for s in self.spans
                              if s.startswith(top + "/")]
        # spans whose enclosing bracket was never opened come last
        order += [s for s in self.spans if s not in order]
        tot = sum(d["seconds"] for d in tops.values())
        timers = {}
        for name in order:
            d = tops.get(name) or dict(self.spans[name])
            d["share"] = d["seconds"] / tot if tot > 0 else 0.0
            timers[name] = d
        return {"timers": timers, "counters": dict(self.counters)}

    def table(self) -> str:
        s = self.summary()
        rows = ["phase/span              seconds   share  brackets"]
        for p, d in s["timers"].items():
            name = "  " * p.count("/") + p
            rows.append(f"{name:<21} {d['seconds']:>9.4f} {d['share']:>6.1%}"
                        f" {d['calls']:>9d}")
        if s["counters"]:
            rows.append("counter                   value")
            rows += [f"{k:<21} {v:>9d}" for k, v in s["counters"].items()]
        return "\n".join(rows)


def span(prof: PhaseProfiler | None, name: str, sync=None):
    """``prof.phase(name, sync)``, or a shared no-op context when no
    profiler is attached (no timer, no annotation, no sync)."""
    return _OFF if prof is None else prof.phase(name, sync)
