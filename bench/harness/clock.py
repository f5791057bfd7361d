"""Compile time from ``jax.monitoring`` and the device's peak memory.

Copied from ``chip_smoke.py`` (CompileClock, peak_bytes) so the yardstick
stays with the benchmark; each compile is kept with its host time so the
window can count the compiles that fell inside it."""
from __future__ import annotations

import time
from typing import List, Tuple

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Backend compiles reported through jax.monitoring: (end time, s)."""

    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self.events: List[Tuple[float, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.events.append((self.clock(), float(duration)))

    def seconds(self, t_from: float = float("-inf"),
                t_to: float = float("inf")) -> float:
        return sum(d for t, d in self.events if t_from <= t < t_to)

    def count(self, t_from: float = float("-inf"),
              t_to: float = float("inf")) -> int:
        return sum(1 for t, _ in self.events if t_from <= t < t_to)


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0
