"""One run of one cell: set-up, pre-roll, the measured window, the traced
slice, the metrics and the correctness check, in one process."""
from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

import numpy as np

from . import check, e2e, seeds
from .driver import Loop, counters
from .spec import Benchmark

WARM_RID = 1 << 30                    # request ids of the warm-up requests


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Options:
    workload: str
    seed: int
    seconds: float
    trace: bool = False


@dataclasses.dataclass
class Serving:
    """What set-up hands to the window: the cell's files and one engine,
    built from the seed and warmed up."""
    bench: Benchmark
    workload: Dict[str, Any]
    cell: Dict[str, Any]
    traffic: Dict[str, Any]
    built: Any
    eng: Any
    devs: List[Any]
    clock: Any
    peaks: Optional[Dict[str, float]]


@dataclasses.dataclass
class Finished:
    prompt: np.ndarray
    tokens: np.ndarray


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""
    cfg: Any
    records: List[Any]
    steps: List[Any]
    t_open: float
    t_end: float
    counters_open: Dict[str, int]
    counters_close: Dict[str, int]
    compile_setup_s: float
    compiles_in_window: int
    peaks: Optional[Dict[str, float]]
    trace: Any = None                     # tracing.Summary of the slice

    def counter(self, name: str) -> int:
        return self.counters_close[name] - self.counters_open[name]

    def steps_in_window(self):
        return [s for s in self.steps if self.t_open <= s.start < self.t_end]


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def devices_or_fail(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return devs[:chips]


def warm_up(eng, vocab: int, rng: np.random.Generator) -> None:
    """Compile the cell's engine programs before the window: the chunk step
    at every width 1..prefill_chunk the traffic can produce, admit, retire,
    and the copy-on-write block copy for up to 4 blocks per step."""
    from repro.launch.engine import Request
    import jax.numpy as jnp
    for t in range(1, eng.prefill_chunk + 1):
        eng.submit(Request(rid=WARM_RID + t,
                           prompt=rng.integers(0, vocab, t).astype(np.int32),
                           max_new_tokens=2))
        while eng.queue or eng.active.any():
            eng.step()
    dump = eng.pool.spec.n_blocks            # the scratch row: copies are no-ops
    for n in range(1, 5):
        idx = jnp.full((n,), dump, jnp.int32)
        eng.cache = eng._copy_blocks(eng.cache, idx, idx)


def drain(eng, drv, limit_s: float, want_tokens: int) -> None:
    """After the window: drop the requests still queued or still in their
    prompt, and let the engine go on decoding the others, through the same
    compiled programs, until the finished requests hold ``want_tokens``
    served tokens, none is left, or ``limit_s`` has passed; so cells whose
    requests outlive the window have finished requests to compare."""
    for req in list(eng.queue):
        eng.cancel(req.rid)
    for s in range(eng.n_slots):
        if eng.slot_req[s] is not None and eng.slot_prefill_off[s] is not None:
            eng.cancel(eng.slot_req[s].rid)

    def served():
        return sum(len(f.tokens) for rid, f in eng.finished.items()
                   if rid in drv.records and f.finish_reason == "length")
    t_stop = time.perf_counter() + limit_s
    while (eng.active.any() and time.perf_counter() < t_stop
           and served() < want_tokens):
        eng.step()


def setup(opts: Options, bench: Optional[Benchmark] = None, *,
          conf: Optional[Dict[str, Any]] = None) -> Serving:
    """Devices, compile cache, weights bound on the device, the engine and
    its warm-up. ``conf`` replaces the configuration file's contents (the
    control runs serve the program under another policy)."""
    bench = bench or Benchmark()
    wl = bench.workload(opts.workload)
    devs = devices_or_fail(wl["chips"])
    import jax
    from repro.launch.engine import ServeEngine
    from .clock import CompileClock
    from . import peaks as peaks_mod
    on_tpu = devs[0].platform == "tpu"
    if on_tpu:
        from repro.launch import compile_cache
        compile_cache.enable()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    clock = CompileClock()
    peaks = peaks_mod.for_kind(devs[0].device_kind) if on_tpu else None
    conf = conf or bench.config_file(wl["config"])
    built = bench.builder(conf).build(conf, opts.seed)
    eng = ServeEngine(built.cfg, built.params, policy=built.policy,
                      **built.engine_kwargs)
    warm_up(eng, built.cfg.vocab_size, seeds.rng(opts.seed, seeds.WARMUP))
    # what set-up left on the heap is never collected again: a full
    # collection over it would stall a step for seconds
    gc.collect()
    gc.freeze()
    return Serving(bench, wl, bench.cell(opts.workload),
                   bench.traffic(wl["traffic"]), built, eng, devs, clock,
                   peaks)


def schedule(srv: Serving, seed: int, horizon_s: float,
             arrivals: Optional[Dict[str, Any]] = None,
             origin_s: float = 0.0):
    """The cell's requests, drawn from the seed (``arrivals`` replaces the
    cell's own, as the knee sweep does); open-loop blocks start at
    ``origin_s``."""
    gen = srv.bench.generator(srv.traffic["generator"])
    return gen.generate(srv.traffic["params"],
                        arrivals=arrivals or srv.cell["arrivals"],
                        rng=seeds.rng(seed, seeds.TRAFFIC),
                        horizon_s=horizon_s, vocab=srv.built.cfg.vocab_size,
                        max_len=srv.eng.max_len, origin_s=origin_s)


def run(opts: Options, t_process: float, *,
        bench: Optional[Benchmark] = None, srv: Optional[Serving] = None,
        reference_bits: Optional[int] = None) -> Dict[str, Any]:
    """One run: set-up (unless ``srv`` is given), pre-roll, the window, the
    traced slice, the metrics, then the check against the plain reference
    (and, with ``reference_bits``, the reference at that operand width as
    the control's reading)."""
    srv = srv or setup(opts, bench)
    bench, cell, traffic = srv.bench, srv.cell, srv.traffic
    eng, devs, clock = srv.eng, srv.devs, srv.clock
    from .clock import peak_bytes
    max_len = eng.max_len
    preroll = float(cell["preroll_s"])
    wait_s = float(cell.get("first_token_wait_s", 0.0))
    sched = schedule(srv, opts.seed, preroll + opts.seconds + wait_s + 60.0,
                     origin_s=preroll)
    requests = {r.rid: r for r in sched}

    t0 = time.perf_counter()
    drv = Loop(eng, sched, t0, annotate=opts.trace)
    t_open = t0 + preroll              # the window opens on the schedule's
    drv.run_until(t_open)              # block boundary
    setup_s = t_open - t_process
    c_open = counters(eng)
    t_end = t_open + opts.seconds

    tracer = None
    if opts.trace:
        from . import tracing
        tracer = tracing.Capture(tempfile.mkdtemp(prefix="bench_trace_"))
        start_at = t_end - float(cell["trace_s"])

        def on_tick(now):
            if not tracer.started and now >= start_at:
                tracer.start()
        drv.run_until(t_end, on_tick)
    else:
        drv.run_until(t_end)
    c_close = counters(eng)
    late = drv.lateness(t_open, t_end)
    log(f"bench: generator lateness over {len(late)} requests due in the "
        f"window: median {np.median(late) * 1e3 if len(late) else 0:.3f} ms, "
        f"max {late.max() * 1e3 if len(late) else 0:.3f} ms")
    if tracer is not None:
        tracer.stop()
    # serve on until every request due in the window has its first token
    due_rids = [r.rid for r in sched
                if r.client < 0 and t_open <= t0 + r.due < t_end]

    def first_tokens_in() -> bool:
        recs = drv.records
        return (all(rid in recs and recs[rid].vis for rid in due_rids)
                and all(r.vis for r in e2e.due_in(recs.values(), t_open,
                                                  t_end)))
    drv.run_until(t_end + wait_s, done=first_tokens_in)
    t_wait = max(t_end, time.perf_counter())
    summary = None
    if tracer is not None:
        summary = tracer.reduce()
        shutil.rmtree(tracer.dir, ignore_errors=True)
    memory_peak = peak_bytes(devs)

    records = list(drv.records.values())
    attempted, failed = e2e.attempted_failed(records, t_open, t_end)
    ctx = Context(srv.built.cfg, records, drv.steps, t_open, t_end,
                  c_open, c_close, clock.seconds(t_to=t_open),
                  clock.count(t_open, t_end), srv.peaks, summary)
    if opts.trace:
        metrics = {}
        for m in bench.per_layer(opts.workload):
            value = bench.metric_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        e2e_vals = e2e.end_to_end(records, t_open, t_end, t_wait)
        e2e_vals["setup_s"] = setup_s
        metrics = {m["name"]: {"value": e2e_vals[m["name"]], "unit": m["unit"]}
                   for m in bench.end_to_end(opts.workload)
                   if e2e_vals.get(m["name"]) is not None}
    log(f"bench: {attempted} requests due in the window, "
        f"{sum(1 for r in records if r.reason)} finished in all, "
        f"{c_close['steps'] - c_open['steps']} engine steps, "
        f"{clock.count(t_open, t_end)} compiles in the window")

    drain(eng, drv, float(cell["check"].get("drain_s", 0.0)),
          int(cell["check"]["min_tokens"]))
    finished = [Finished(requests[rid].prompt, np.asarray(f.tokens))
                for rid, f in eng.finished.items()
                if rid in drv.records and f.finish_reason in ("length", "eos")]
    items = check.sample(finished, seeds.rng(opts.seed, seeds.SAMPLE),
                         int(cell["check"]["min_tokens"]),
                         int(cell["check"]["max_requests"]))
    # the program's state goes before the reference takes the chip
    srv.eng = srv.built = None
    del eng, drv
    gc.collect()
    conf = bench.config_file(srv.workload["config"])
    builder = bench.builder(conf)
    ref = builder.reference(conf, opts.seed)
    control = None
    if reference_bits:
        qw, _, arith = builder.reference(conf, opts.seed,
                                         n_bits=reference_bits)
        control = {"qw": qw, "arith": arith}
    readings = check.compare(ref, items, seq_len=max_len,
                             n_read=int(traffic["params"]["answer"]["max"]),
                             control=control)
    correct, numbers = check.judge(readings, cell["check"])
    if failed:
        correct = False
    for name, extra in readings.items():
        if name not in numbers:
            log(f"bench: {name} {extra}")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    out: Dict[str, Any] = {"correct": bool(correct), "attempted": attempted,
                           "failed": failed, "metrics": metrics,
                           "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    out["readings"] = {k: v for k, v in readings.items() if k not in numbers}
    for name, num in numbers.items():
        log(f"check {name}: {num['value']} (limit {num['holds']} "
            f"{num['limit']})")
    out["check"] = numbers
    return out
