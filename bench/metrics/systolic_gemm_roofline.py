"""Kernels: the exact int8 GEMM kernel (``kernels/systolic_gemm.py``) against
its roofline on the chip, in %.

Kernel time is the sum of the device durations of its calls in the traced
slice: the Pallas custom calls that XLA names ``systolic_matmul.<n>`` after
the jitted wrapper. Each call's work is that of the GEMM the PE array
computes, read from the call's shapes: 2*M*K*N int8 operations and
M*K + K*N bytes of int8 operands plus 4*M*N bytes of int32 result. The
least time of a call is the larger of operations over the int8 peak and
bytes over the HBM bandwidth; the share is the least time of all calls
over their measured time. At the cells' shapes (M = 16 slots x chunk width,
K and N in the thousands) the operand bytes bound it, not the operations.
"""
import re

SHAPE = re.compile(r"([su](?:8|32))\[(\d+),(\d+)\]")


def ops_bytes(m: int, k: int, n: int):
    return 2.0 * m * k * n, float(m * k + k * n + 4 * m * n)


def call_shape(text: str):
    """(M, K, N) of one call from its HLO text: the s32[M,N] result and the
    s8[M,K], s8[K,N] operands."""
    shapes = SHAPE.findall(text.split(" custom_call_target")[0])
    (_, m, n), (_, m2, k), (_, k2, n2) = shapes[:3]
    m, n, k = int(m), int(n), int(k)
    if (int(m2), int(k2), int(n2)) != (m, k, n):
        raise ValueError(f"unexpected systolic_matmul shapes in {text[:200]}")
    return m, k, n


def read(ctx):
    if ctx.trace is None or not ctx.peaks:
        return None
    calls = ctx.trace.kernel_calls("systolic_matmul")
    if not calls:
        return None
    least = 0.0
    for op in calls:
        ops, nbytes = ops_bytes(*call_shape(op.name))
        least += max(ops / ctx.peaks["int8_ops_per_s"],
                     nbytes / ctx.peaks["hbm_bytes_per_s"])
    measured = sum(op.dur_ns for op in calls) * 1e-9
    return 100.0 * least / measured
