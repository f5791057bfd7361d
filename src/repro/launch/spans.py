"""Phase spans of the serve engine's step.

``Phases(counters)("prepare")`` is a context manager that

* enters ``jax.profiler.TraceAnnotation("engine.prepare")``, so a profiler
  session puts the phase on its host timeline, on the device trace's clock
  (with no session active the annotation records nothing), and
* adds the phase's *self* time, in integer nanoseconds of
  ``time.perf_counter_ns()``, to ``counters["prepare_ns"]``: while a phase
  nested inside it runs, the outer phase's clock stops, so the counters
  never count a nanosecond twice and their sum is the time spent in phases.

The engine's phases (docs/serving.md, "Step phases"):

* ``scrub``    the ABFT guard's integrity checks and fingerprint refreshes;
* ``admit``    deadlines and admission: prefix plan, pool reservation and
  the admit dispatch;
* ``prepare``  the step's inputs: tokens, ``q_len`` and ``emit``, block
  allocation, copy-on-write and the host-to-device uploads;
* ``dispatch`` the jitted chunk step or horizon, until the call returns;
* ``sync``     the wait for the sampled tokens on the host;
* ``commit``   the host's bookkeeping: counters, outputs, retirement and
  prefix publication;
* ``backoff``  the wait between retries of a failed step.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator, List

import jax

PHASES = ("scrub", "admit", "prepare", "dispatch", "sync", "commit",
          "backoff")
SPAN_PREFIX = "engine."


def counter(phase: str) -> str:
    """The counter a phase's self time adds to."""
    return phase + "_ns"


class Phases:
    """Records the engine's phases into ``counters`` (see the module doc)."""

    def __init__(self, counters: Dict[str, int]):
        self.counters = counters
        for phase in PHASES:
            counters.setdefault(counter(phase), 0)
        self._open: List[str] = []       # counters of the open phases
        self._mark = 0                   # when the innermost one resumed

    def _charge(self) -> None:
        now = time.perf_counter_ns()
        if self._open:
            self.counters[self._open[-1]] += now - self._mark
        self._mark = now

    @contextlib.contextmanager
    def __call__(self, phase: str) -> Iterator[None]:
        self._charge()
        self._open.append(counter(phase))
        try:
            with jax.profiler.TraceAnnotation(SPAN_PREFIX + phase):
                yield
        finally:
            self._charge()
            self._open.pop()
