"""A profiler trace of a slice of the window, reduced to device busy time,
per-op device time and the idle gaps between ops.

The reduction reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
nothing but ``jax.profiler.ProfileData``:

* device ops are the events on the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane, named by their HLO text; busy time is the
  union of their intervals. Control-flow ops (``while``, ``conditional``,
  ``call``) hold the ops of their bodies, which the line lists too, so they
  count towards busy time but not in the per-op breakdown;
* the window is the span from the first to the last of the harness's own
  host spans (``bench.*`` TraceAnnotations on the ``/host:CPU`` plane);
  the profiler puts host and device events on one clock;
* each idle gap (time inside the window with no device op) is tagged with
  the innermost harness span that holds its midpoint and the innermost host
  event of the ``python`` thread there (a dispatch, a transfer, the sync).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
PYTHON_LINE = "python"
CONTAINERS = ("while", "conditional", "call")
_HLO = re.compile(r"^%?([\w.\-]+) = (.+?) ([\w\-]+)\(")


def hlo_parts(text: str):
    """(instruction name, result shape, opcode) of an HLO op's text, or the
    text itself as the name where it does not parse."""
    m = _HLO.match(text)
    if not m:
        return text, "", ""
    return m.group(1), m.group(2), m.group(3)


def label(text: str) -> str:
    """A short name for an op: its instruction name without the numeric
    suffix XLA adds, and its result shape without layouts."""
    name, shape, _ = hlo_parts(text)
    base = re.sub(r"\.\d+$", "", name)
    shape = re.sub(r"\{[^}]*\}", "", shape)
    return (base + " " + shape)[:120].strip()


@dataclasses.dataclass
class Op:
    name: str
    start_ns: float
    dur_ns: float
    stats: Dict[str, str]


@dataclasses.dataclass
class Summary:
    busy_s: float
    window_s: float
    ops: List[Op]                       # device ops inside the window
    spans: List[Tuple[str, float, float]]   # harness host spans (ns)
    gaps: List[Tuple[str, float]]       # (host span tag, seconds)
    n_devices: int = 1

    def leaf_ops(self) -> List[Op]:
        return [op for op in self.ops
                if hlo_parts(op.name)[2] not in CONTAINERS]

    def op_seconds(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for op in self.leaf_ops():
            key = label(op.name)
            out[key] = out.get(key, 0.0) + op.dur_ns * 1e-9 / self.n_devices
        return out

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:top]
        by_tag: Dict[str, float] = {}
        for tag, sec in self.gaps:
            by_tag[tag] = by_tag.get(tag, 0.0) + sec
        gaps = sorted(by_tag.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}

    def kernel_calls(self, prefix: str) -> List[Op]:
        """Events of the Pallas kernel whose custom call XLA names
        ``<prefix>.<n>`` (the name of the jitted function around the
        ``pallas_call``)."""
        return [op for op in self.leaf_ops()
                if hlo_parts(op.name)[2] == "custom-call"
                and re.sub(r"\.\d+$", "", hlo_parts(op.name)[0]) == prefix]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def _innermost(spans, t: float):
    best = None
    for name, a, b in spans:
        if a <= t <= b and (best is None or b - a < best[2] - best[1]):
            best = (name, a, b)
    return best[0] if best else None


def _tag(spans, host, t: float) -> str:
    outer = _innermost(spans, t) or "outside harness spans"
    inner = _innermost(host, t)
    return outer if inner is None else f"{outer}/{inner}"


def reduce_planes(planes) -> Optional[Summary]:
    """Summary of the trace, or None when it holds no device plane or no
    harness span (nothing to read)."""
    spans: List[Tuple[str, float, float]] = []
    host: List[Tuple[str, float, float]] = []
    per_device: List[List[Op]] = []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    iv = (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(iv)
                    elif line.name == PYTHON_LINE:
                        host.append(iv)
        elif plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append(Op(ev.name, ev.start_ns, ev.duration_ns,
                                  {str(k): str(v) for k, v in ev.stats}))
            per_device.append(ops)
    if not spans or not per_device:
        return None
    lo = min(a for _, a, _ in spans)
    hi = max(b for _, _, b in spans)
    busy_s, kept = 0.0, []
    gaps: List[Tuple[str, float]] = []
    for i, ops in enumerate(per_device):
        busy = _clip(_union([(o.start_ns, o.start_ns + o.dur_ns)
                             for o in ops]), lo, hi)
        busy_s += sum(b - a for a, b in busy) * 1e-9
        kept += [o for o in ops if lo <= o.start_ns < hi]
        if i == 0:
            edges = [lo] + [t for iv in busy for t in iv] + [hi]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    gaps.append((_tag(spans, host, (a + b) / 2),
                                 (b - a) * 1e-9))
    n = len(per_device)
    return Summary(busy_s / n, (hi - lo) * 1e-9, kept, spans, gaps, n)


def reduce_file(path: str) -> Optional[Summary]:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)


class Capture:
    """Profiler session around the traced slice, written under ``dir``."""

    def __init__(self, directory: str):
        self.dir = directory
        self.started = False

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.started = True

    def stop(self) -> None:
        if self.started:
            import jax
            jax.profiler.stop_trace()

    def reduce(self) -> Optional[Summary]:
        if not self.started:
            return None
        paths = sorted(glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                                 recursive=True))
        return reduce_file(paths[-1]) if paths else None
