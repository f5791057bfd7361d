"""Phase spans and queue counters of the serve engine's step.

* Every path through ``ServeEngine.step()`` (paged chunk step, multi-step
  horizon, contiguous decode) times its phases into ``occ["<phase>_ns"]``:
  non-negative integers that only grow, whose sum covers the wall time of
  the ``step()`` calls that produced them.
* ``occ["admitted"]`` counts every admission, a preempted request's
  re-admission too, and ``occ["queue_wait_ns"]`` sums each one's wait from
  submit (or from its preemption) to admission.
* Under a profiler session the phases are ``engine.<phase>`` annotations on
  the calling thread's host line, nested inside the harness's
  ``bench.step``, so the benchmark's trace reduction, given that line's
  events, tags an idle gap there with the phase.
"""
import dataclasses
import gc
import glob
import os
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.launch import engine as E
from repro.launch import spans
from repro.models import get_model

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.harness import tracing  # noqa: E402

CFG = reduced(ARCHS["smollm-360m"])
PARAMS = get_model(CFG).init_params(jax.random.PRNGKey(0))
# (prompt, answer) lengths
LENS = tuple((5 + 4 * (i % 3), 3 + i % 5) for i in range(24))
# wide enough that computing a step outweighs the host's share of it, as on
# the chip: what runs outside a phase is then a small enough share of the
# wall time that a loaded test machine does not push it past the bound
WIDE = dataclasses.replace(CFG, d_model=256, d_ff=1024, head_dim=64)
COUNTERS = [spans.counter(p) for p in spans.PHASES]


def mkreqs(lens=LENS):
    rng = np.random.default_rng(0)
    return [E.Request(rid=i, prompt=rng.integers(
                0, CFG.vocab_size, pl).astype(np.int32), max_new_tokens=gl)
            for i, (pl, gl) in enumerate(lens)]


def mkengine(cfg=CFG, params=PARAMS, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_len", 24)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_chunk", 4)
    return E.ServeEngine(cfg, params, **kw)


def drive(eng, reqs):
    """Serve ``reqs`` one ``step()`` at a time; returns the summed wall time
    of the calls and the phase counters after each one. The collector is off,
    as the benchmark freezes the heap after its warm-up: a full collection
    would land in a step wherever it falls."""
    for r in reqs:
        eng.submit(r)
    wall, seen = 0, []
    gc.collect()
    gc.disable()
    try:
        while eng.queue or eng.active.any():
            t0 = time.perf_counter_ns()
            eng.step()
            wall += time.perf_counter_ns() - t0
            seen.append({k: eng.occ[k] for k in COUNTERS})
    finally:
        gc.enable()
    return wall, seen


@pytest.mark.parametrize("kw, phases", [
    ({}, ("admit", "prepare", "dispatch", "sync", "commit")),
    ({"multi_step": 4}, ("admit", "prepare", "dispatch", "sync", "commit")),
    ({"paged": False}, ("admit", "dispatch", "sync", "commit")),
], ids=["paged", "multi_step", "contiguous"])
def test_phase_counters_grow_and_cover_the_step(kw, phases):
    eng = mkengine(WIDE, get_model(WIDE).init_params(jax.random.PRNGKey(0)),
                   **kw)
    drive(eng, mkreqs())                 # compiles every program it needs
    before = {k: eng.occ[k] for k in COUNTERS}
    wall, seen = drive(eng, mkreqs())
    prev = before
    for now in seen:
        for k in COUNTERS:
            assert isinstance(now[k], int) and now[k] >= prev[k] >= 0, k
        prev = now
    spent = {k: seen[-1][k] - before[k] for k in COUNTERS}
    assert all(spent[spans.counter(p)] > 0 for p in phases), spent
    assert spent["scrub_ns"] == spent["backoff_ns"] == 0   # no guard, no retry
    # the phases do not overlap, and leave only the step's few lines
    # between them uncounted
    assert 0.95 * wall <= sum(spent.values()) <= wall
    st = eng.stats
    assert st["sync_ms"] == pytest.approx(eng.occ["sync_ns"] * 1e-6, abs=1e-3)


def test_admissions_count_readmissions_and_their_waits():
    """3 slots over a 3-block pool: a late high-priority request preempts,
    and the preempted request is admitted again."""
    eng = mkengine(max_slots=3, n_blocks=3, block_size=8, max_len=16)
    reqs = mkreqs(((5, 4), (8, 6), (3, 5), (6, 3)))
    reqs[3].priority, reqs[3].arrival = 5, 2
    fin = eng.run(reqs)
    assert eng.events["preemptions"] >= 1
    assert all(f.finish_reason in ("eos", "length") for f in fin.values())
    assert eng.occ["admitted"] == len(reqs) + eng.events["preemptions"]
    assert eng.occ["queue_wait_ns"] > 0
    assert eng.stats["queue_wait_mean_ms"] == pytest.approx(
        eng.occ["queue_wait_ns"] * 1e-6 / eng.occ["admitted"], abs=1e-3)


def test_queue_wait_runs_from_submit_to_admission():
    eng = mkengine()
    eng.run(mkreqs(LENS[:1]))            # compiled
    wait0, n0 = eng.occ["queue_wait_ns"], eng.occ["admitted"]
    req = mkreqs(LENS[1:2])[0]
    eng.submit(req)
    time.sleep(0.05)
    eng.step()
    waited = eng.occ["queue_wait_ns"] - wait0
    assert eng.occ["admitted"] == n0 + 1
    assert 50e6 <= waited <= time.perf_counter_ns() - req.queued_ns


def _free_point(ev, host):
    """The midpoint of the longest stretch of ``ev`` that no shorter event
    of the line covers."""
    inner = sorted((a, b) for _, a, b in host
                   if ev[1] <= a and b <= ev[2] and (b - a) < ev[2] - ev[1])
    best, cur = (0, ev[1]), ev[1]
    for a, b in inner + [(ev[2], ev[2])]:
        if a - cur > best[0]:
            best = (a - cur, cur)
        cur = max(cur, b)
    return best[1] + best[0] / 2


def test_profiler_trace_tags_idle_time_with_the_phase(tmp_path):
    eng = mkengine()
    reqs = mkreqs()
    for r in reqs[:2]:
        eng.submit(r)
    eng.step()                                # compiled before the capture
    for r in reqs[2:]:
        eng.submit(r)
    cap = tracing.Capture(str(tmp_path))
    cap.start()
    try:
        for _ in range(4):
            with jax.profiler.TraceAnnotation("bench.step"):
                eng.step()
    finally:
        cap.stop()
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[-1]
    # the thread's own line, which the profiler names after the process
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in ln.events]
             for pl in ProfileData.from_file(path).planes
             if pl.name.startswith("/host:") for ln in pl.lines]
    evs = [evs for evs in lines if any(e[0] == "bench.step" for e in evs)][0]
    steps = [e for e in evs if e[0] == "bench.step"]
    host = [e for e in evs if not e[0].startswith(tracing.SPAN_PREFIX)]
    phases = [e for e in host if e[0].startswith(spans.SPAN_PREFIX)]
    assert len(steps) == 4
    assert {n for n, _, _ in phases} >= {
        "engine.admit", "engine.prepare", "engine.dispatch", "engine.sync",
        "engine.commit"}
    for ev in phases:
        assert any(a <= ev[1] and ev[2] <= b for _, a, b in steps), ev
        assert tracing._tag(steps, host, _free_point(ev, host)) == (
            "bench.step/" + ev[0])
