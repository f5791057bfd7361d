"""The readers of the engine's phase and queue counters: on a synthetic
``Context``, on one without the counters (a program that keeps none), and
in a traced run of the tiny cell on the CPU."""
import time

import pytest

from bench.harness import spec
from bench.harness.cell import Context
from bench.harness.driver import Step
from bench.tests import tiny

BENCH = spec.Benchmark()
PHASES = ("scrub", "admit", "prepare", "dispatch", "sync", "commit",
          "backoff")
NEW = ("step_host_ms", "step_host_ms.rag", "step_sync_ms",
       "step_sync_ms.rag", "engine_queue_wait_ms")


def read(name, ctx):
    return BENCH.metric_reader(name).read(ctx)


def counters(scale, **extra):
    out = {p + "_ns": scale * (i + 1) * 1_000_000
           for i, p in enumerate(PHASES)}
    out.update(extra)
    return out


def ctx_for(steps, c_open, c_close):
    return Context(None, [], steps, 10.0, 20.0, c_open, c_close, 0.0, 0,
                   None)


def test_phase_and_queue_readers():
    steps = [Step(10.0 + i, 10.5 + i, 8, 800.0) for i in range(10)]
    steps.append(Step(25.0, 26.0, 8, 800.0))          # after the window
    c0 = counters(1, queue_wait_ns=5_000_000, admitted=3)
    c1 = counters(3, queue_wait_ns=45_000_000, admitted=7)
    ctx = ctx_for(steps, c0, c1)
    # each phase grew by 2 * (i + 1) ms over 10 steps in the window
    host = sum(2 * (i + 1) for i, p in enumerate(PHASES) if p != "sync")
    for suffix in ("", ".rag"):
        assert read("step_host_ms" + suffix, ctx) == pytest.approx(host / 10)
        assert read("step_sync_ms" + suffix, ctx) == pytest.approx(2 * 5 / 10)
    assert read("engine_queue_wait_ms", ctx) == pytest.approx(40.0 / 4)


@pytest.mark.parametrize("case", ["no_step", "no_admission", "no_counters"])
def test_nothing_to_read(case):
    steps = [Step(10.0 + i, 10.5 + i, 8, 800.0) for i in range(3)]
    c0 = counters(1, queue_wait_ns=0, admitted=2)
    c1 = counters(2, queue_wait_ns=0, admitted=2)
    if case == "no_step":
        steps = [Step(25.0, 26.0, 8, 800.0)]
    if case == "no_counters":               # a program without the counters
        c0 = c1 = {"slot_steps": 0, "slot_active_steps": 0}
    ctx = ctx_for(steps, c0, c1)
    got = {name: read(name, ctx) for name in NEW}
    if case == "no_admission":
        assert got.pop("engine_queue_wait_ms") is None
        assert all(v is not None for v in got.values())
    else:
        assert got == {name: None for name in NEW}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny.make_tree(tmp_path_factory.mktemp("bench"))


def test_traced_run_splits_the_step(tree, monkeypatch):
    import jax
    from bench.harness import cell
    monkeypatch.setattr(cell, "devices_or_fail",
                        lambda chips: jax.devices()[:chips])
    bm, name = tree
    t0 = time.perf_counter()
    opts = cell.Options(name, 7, 1.0, trace=True)
    out = cell.run(opts, t0, srv=cell.setup(opts, bm))
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(NEW) <= set(m)
    assert m["step_host_ms"] > 0 and m["step_sync_ms"] > 0
    assert m["engine_queue_wait_ms"] >= 0
    # the phases lie inside the harness's timing of each step
    split = m["step_host_ms"] + m["step_sync_ms"]
    assert 0.9 * m["engine_step_ms"] <= split <= m["engine_step_ms"]
