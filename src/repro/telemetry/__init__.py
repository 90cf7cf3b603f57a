"""Telemetry layer: decision traces, carbon attribution, phase profiling.

See README §Observability.  Everything here is observation-only: the
engines behave bit-identically with telemetry attached or absent."""
from .attribution import CAUSES, Attribution, attribute
from .events import (EVENT_KINDS, MemoryRecorder, SlotEventTracker,
                     Telemetry, TraceEvent, TraceRecorder,
                     emit_fault_events)
from .profiler import PHASES, PhaseProfiler, span
from .report import explain

__all__ = [
    "CAUSES", "Attribution", "attribute",
    "EVENT_KINDS", "MemoryRecorder", "SlotEventTracker", "Telemetry",
    "TraceEvent", "TraceRecorder", "emit_fault_events",
    "PHASES", "PhaseProfiler", "span",
    "explain",
]
