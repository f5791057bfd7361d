"""The client loop: submit each request when it is due, between
``ServeEngine.step()`` calls, and record when its tokens become visible on
the host. An open-loop request is due at its scheduled time on the wall
clock; a closed-loop request (one with a ``client``) is due when its
client's previous request has finished, or at the start for its first.

Everything is read from the engine's public state after each step
(``slot_req``, ``slot_out``, ``slot_pos``, ``finished``, ``occ``,
``prefix_events``); nothing in the program is changed or wrapped.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np


@dataclasses.dataclass
class Record:
    rid: int
    due: float                   # absolute host time
    prompt_len: int
    max_new: int
    submit: Optional[float] = None
    admit: Optional[float] = None        # start of the first step holding it
    vis: List[Tuple[float, int]] = dataclasses.field(default_factory=list)
    finish: Optional[float] = None
    reason: Optional[str] = None

    @property
    def n(self) -> int:
        return self.vis[-1][1] if self.vis else 0


@dataclasses.dataclass
class Step:
    start: float
    end: float
    tokens: int                  # prompt + decode tokens the step processed
    context: float               # sum over those tokens of (position + 1)


def counters(eng) -> Dict[str, int]:
    out = dict(eng.occ)
    out.update(eng.prefix_events)
    out["steps"] = eng.step_count
    return out


class Loop:
    def __init__(self, eng, schedule, t0: float, *, annotate: bool = False,
                 clock: Callable[[], float] = time.perf_counter,
                 sleep: Callable[[float], None] = time.sleep):
        from repro.launch import engine as engine_mod
        self._request = engine_mod.Request
        self.eng = eng
        self.schedule = schedule
        self.t0 = t0
        self.clock = clock
        self.sleep = sleep
        self.records: Dict[int, Record] = {}
        self.steps: List[Step] = []
        self.open = [r for r in schedule if r.client < 0]
        self.next = 0
        self.clients: Dict[int, List] = {}
        for r in schedule:
            if r.client >= 0:
                self.clients.setdefault(r.client, []).append(r)
        # when each client's next request is due; None while one is in flight
        self.free_at: Dict[int, Optional[float]] = {c: t0
                                                    for c in self.clients}
        self._client_of = {r.rid: r.client for r in schedule}
        self._fin_seen = len(eng.finished)     # what finished before us
        self._slots: Dict[int, Tuple[int, int]] = {}
        if annotate:
            import jax
            self.span = jax.profiler.TraceAnnotation
        else:
            self.span = lambda name: contextlib.nullcontext()

    def _submit(self, r, due: float) -> None:
        rec = Record(r.rid, due, len(r.prompt), r.max_new)
        self.records[r.rid] = rec
        self.eng.submit(self._request(rid=r.rid, prompt=r.prompt,
                                      max_new_tokens=r.max_new))
        rec.submit = self.clock()

    def _submit_due(self, now: float) -> None:
        while (self.next < len(self.open)
               and self.t0 + self.open[self.next].due <= now):
            r = self.open[self.next]
            self.next += 1
            self._submit(r, self.t0 + r.due)
        for c, due in self.free_at.items():
            if due is not None and due <= now and self.clients[c]:
                self.free_at[c] = None
                self._submit(self.clients[c].pop(0), due)

    def next_due(self) -> float:
        due = [t for c, t in self.free_at.items()
               if t is not None and self.clients[c]]
        if self.next < len(self.open):
            due.append(self.t0 + self.open[self.next].due)
        return min(due, default=float("inf"))

    def run_until(self, t_stop: float,
                  on_tick: Optional[Callable[[float], None]] = None,
                  done: Optional[Callable[[], bool]] = None) -> None:
        """Serve the schedule until ``t_stop``, or until ``done()`` holds."""
        eng = self.eng
        while True:
            now = self.clock()
            if now >= t_stop or (done is not None and done()):
                return
            if on_tick is not None:
                on_tick(now)
            with self.span("bench.submit"):
                self._submit_due(now)
            if not eng.queue and not eng.active.any():
                with self.span("bench.wait"):
                    wait = min(self.next_due(), t_stop) - self.clock()
                    if wait > 0:
                        self.sleep(wait)
                continue
            before = counters(eng)
            t_s = self.clock()
            with self.span("bench.step"):
                eng.step()
            t_e = self.clock()
            with self.span("bench.record"):
                self._record(t_s, t_e, before)

    def _record(self, t_s: float, t_e: float, before: Dict[str, int]) -> None:
        eng = self.eng
        after = counters(eng)
        tokens = ((after["prefill_tokens"] + after["decode_tokens"])
                  - (before["prefill_tokens"] + before["decode_tokens"]))
        chunk = eng.prefill_chunk
        context = 0.0
        now_slots: Dict[int, Tuple[int, int]] = {}
        for s in range(eng.n_slots):
            req = eng.slot_req[s]
            if req is None:
                continue
            rec = self.records[req.rid]
            if rec.admit is None:
                rec.admit = t_s
            pos = int(eng.slot_pos[s])
            prev = self._slots.get(s)
            q = (pos - prev[1] if prev is not None and prev[0] == req.rid
                 else min(chunk, pos))
            context += q * pos - q * (q - 1) / 2
            now_slots[s] = (req.rid, pos)
            n = len(eng.slot_out[s])
            if n > rec.n:
                rec.vis.append((t_e, n))
        for s, (rid, pos) in self._slots.items():
            if now_slots.get(s, (None,))[0] != rid:
                context += pos + 1           # its last token, then retired
        self._slots = now_slots
        fin = eng.finished
        if len(fin) > self._fin_seen:
            for f in list(fin.values())[self._fin_seen:]:
                rec = self.records[f.rid]
                rec.finish = t_e
                rec.reason = f.finish_reason
                client = self._client_of.get(f.rid, -1)
                if client >= 0:
                    self.free_at[client] = t_e
                if len(f.tokens) > rec.n:
                    rec.vis.append((t_e, len(f.tokens)))
            self._fin_seen = len(fin)
        self.steps.append(Step(t_s, t_e, tokens, context))

    def lateness(self, t_from: float, t_to: float) -> np.ndarray:
        return np.array([r.submit - r.due for r in self.records.values()
                         if t_from <= r.due < t_to and r.submit is not None])
