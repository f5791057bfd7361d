"""Trace reduction: busy time, idle gaps, per-op time and the kernel
roofline, on synthetic planes and on a short trace recorded on a TPU v5e
(``data/systolic_v5e.xplane.pb``: three calls of a bound mxu_int8 GEMM,
512 x 960 x 2560, each inside a ``bench.step`` span)."""
import os
from types import SimpleNamespace as NS

import pytest

from bench.harness import spec, tracing

DATA = os.path.join(os.path.dirname(__file__), "data", "systolic_v5e.xplane.pb")
PEAKS = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=[])


def planes():
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[ev("bench.step", 0, 1000),
                                  ev("PjitFunction(chunk)", 100, 50),
                                  ev("np.asarray(jax.Array)", 600, 350),
                                  ev("bench.record", 1000, 200)])])
    loop = "%while.1 = (s32[]) while((s32[]) %t), body=%b"
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_chunk(1)", 150, 500)]),
        NS(name="XLA Ops", events=[
            ev(loop, 150, 500),
            ev("%fusion.3 = s8[4,8]{1,0} fusion(s32[4,8] %a)", 150, 100),
            ev("%systolic_matmul.2 = s32[16,64]{1,0} custom-call(s8[16,32]"
               "{1,0} %x, s8[32,64]{1,0} %y), custom_call_target="
               "\"tpu_custom_call\"", 300, 300),
            ev("%copy.9 = f32[2] copy(f32[2] %z)", 1100, 50)])])
    return [host, dev]


def test_busy_is_the_union_of_device_ops_inside_the_window():
    s = tracing.reduce_planes(planes())
    assert s.window_s == pytest.approx(1200e-9)
    # the while op holds the fusion and the kernel: busy 150..650, 1100..1150
    assert s.busy_s == pytest.approx(550e-9)
    # each gap is tagged at its midpoint: 0..150 in the step, 650..1100 in
    # the host's sync, 1150..1200 in the harness's bookkeeping
    gaps = dict(s.breakdown()["idle_gaps"])
    assert gaps == pytest.approx({"bench.step": 150e-9,
                                  "bench.step/np.asarray(jax.Array)": 450e-9,
                                  "bench.record": 50e-9})


def test_breakdown_counts_leaf_ops_by_short_name():
    s = tracing.reduce_planes(planes())
    ops = dict(s.breakdown()["device_ops"])
    assert set(ops) == {"fusion s8[4,8]", "systolic_matmul s32[16,64]",
                        "copy f32[2]"}
    assert ops["systolic_matmul s32[16,64]"] == pytest.approx(300e-9)


def test_nothing_to_read_without_spans_or_a_device():
    host, dev = planes()
    assert tracing.reduce_planes([dev]) is None
    assert tracing.reduce_planes([host]) is None


def test_roofline_from_kernel_shapes():
    reader = spec.Benchmark().metric_reader("systolic_gemm_roofline")
    s = tracing.reduce_planes(planes())
    m, k, n = 16, 32, 64
    assert reader.call_shape(s.kernel_calls("systolic_matmul")[0].name) == (
        m, k, n)
    least = max(2 * m * k * n / PEAKS["int8_ops_per_s"],
                (m * k + k * n + 4 * m * n) / PEAKS["hbm_bytes_per_s"])
    got = reader.read(NS(trace=s, peaks=PEAKS))
    assert got == pytest.approx(100 * least / 300e-9)
    assert reader.read(NS(trace=None, peaks=PEAKS)) is None


def test_recorded_chip_trace():
    s = tracing.reduce_file(DATA)
    assert len(s.spans) == 3 and s.n_devices == 1
    assert 0 < s.busy_s <= s.window_s
    calls = s.kernel_calls("systolic_matmul")
    assert calls
    reader = spec.Benchmark().metric_reader("systolic_gemm_roofline")
    assert reader.call_shape(calls[0].name) == (512, 1024, 2560)
    share = reader.read(NS(trace=s, peaks=PEAKS))
    assert 0 < share <= 100
    names = [n for n, _ in s.breakdown()["device_ops"]]
    assert "systolic_matmul s32[512,2560]" in names
