"""Reduce a JAX profiler trace to what the benchmark's metrics read.

    summary = reduce_trace(xplane_path, ["window", "run", ...])
    summary.window            # (start, end) ns of the host span "window"
    summary.devices[i].busy   # merged intervals in which an op ran
    summary.op_time(pattern)  # summed device time of ops matching a regex
    summary.idle_gaps()       # gaps in the window, named by the host span

Device planes are those named `/device:TPU:<i>`; their ops are the
events of the line `XLA Ops`, each named `<module>/<instruction>` (the
XLA module running at its start, from the line `XLA Modules`, and the
HLO instruction's name, e.g. `jit_lanes(123)/%masked_round_gradient.8`).
Host spans are the benchmark's own `jax.profiler.TraceAnnotation`s,
found by name on the host plane.  Every time is in the trace's own
nanoseconds, which the profiler puts on one clock for host and device.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"

Interval = Tuple[float, float]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals, as sorted disjoint intervals."""
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def covered(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of [lo, hi] that merged intervals cover."""
    i = max(0, bisect.bisect_right(merged, (lo, float("inf"))) - 1)
    total = 0.0
    for a, b in merged[i:]:
        if a >= hi:
            break
        total += max(0.0, min(b, hi) - max(a, lo))
    return total


@dataclasses.dataclass
class Device:
    index: int
    ops: List[Tuple[str, float, float]]   # (name, start, end) ns
    busy: List[Interval]                  # merged op intervals


@dataclasses.dataclass
class TraceSummary:
    window: Interval
    devices: List[Device]
    spans: Dict[str, List[Interval]]      # host spans by name

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def busy_ns(self, dev: Device, lo: Optional[float] = None,
                hi: Optional[float] = None) -> float:
        lo = self.window[0] if lo is None else lo
        hi = self.window[1] if hi is None else hi
        return covered(dev.busy, lo, hi)

    def op_time(self, pattern: str) -> List[float]:
        """Per device, the summed ns of ops whose name matches `pattern`
        and that start inside the window."""
        rx = re.compile(pattern)
        lo, hi = self.window
        return [sum(e - s for name, s, e in dev.ops
                    if lo <= s < hi and rx.search(name))
                for dev in self.devices]

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        """The k op names with the most device time, in seconds."""
        tot: Dict[str, float] = defaultdict(float)
        lo, hi = self.window
        for dev in self.devices:
            for name, s, e in dev.ops:
                if lo <= s < hi:
                    tot[name] += e - s
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [(name, ns * 1e-9) for name, ns in top]

    def idle_gaps(self, k: int = 10) -> List[Tuple[str, float]]:
        """The k longest gaps between device ops inside the window, over
        all devices, each named by the innermost host span open at its
        middle ("none" where no span is), in seconds."""
        gaps = []
        lo, hi = self.window
        for dev in self.devices:
            edge = lo
            for a, b in dev.busy:
                if b <= lo:
                    continue
                if a >= hi:
                    break
                if a > edge:
                    gaps.append((edge, a))
                edge = max(edge, b)
            if edge < hi:
                gaps.append((edge, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [(self.span_at(0.5 * (a + b)), (b - a) * 1e-9)
                for a, b in gaps[:k]]

    def span_at(self, t: float) -> str:
        best, width = "none", float("inf")
        for name, ivs in self.spans.items():
            if name == "window":
                continue
            for a, b in ivs:
                if a <= t < b and b - a < width:
                    best, width = name, b - a
        return best


def reduce_trace(path: str, span_names: Sequence[str]) -> TraceSummary:
    """Read an `.xplane.pb` and keep device ops and the named host spans
    (which must include "window")."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: List[Device] = []
    spans: Dict[str, List[Interval]] = defaultdict(list)
    want = set(span_names)
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            mods = sorted((float(e.start_ns), e.name)
                          for e in lines[MODULES_LINE].events) \
                if MODULES_LINE in lines else []
            starts = [t for t, _ in mods]

            def module(t):
                i = bisect.bisect_right(starts, t) - 1
                return mods[i][1] if i >= 0 else "?"

            ops = []
            if OPS_LINE in lines:
                for e in lines[OPS_LINE].events:
                    t = float(e.start_ns)
                    name = e.name.split(" = ", 1)[0]
                    ops.append((f"{module(t)}/{name}", t,
                                t + float(e.duration_ns)))
            devices.append(Device(int(m.group(1)), ops,
                                  merge((s, e) for _, s, e in ops)))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name in want:
                        spans[e.name].append(
                            (float(e.start_ns),
                             float(e.start_ns + e.duration_ns)))
    devices.sort(key=lambda d: d.index)
    win = spans.get("window")
    if not win:
        raise ValueError(f"{path}: no host span named 'window'")
    return TraceSummary(window=win[0], devices=devices,
                        spans={k: sorted(v) for k, v in spans.items()})
