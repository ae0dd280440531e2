"""The program's own host spans in the trace a `--trace 1` run wrote.

The program marks each layer's host work with a span named in
`repro.obs.SPANS` (`repro.sample`, `repro.stage`, ...), a
`jax.profiler.TraceAnnotation` whose keywords (`lanes`, `epochs`, ...)
are the event's stats.  The readers of the per-layer metrics that need
them call `spans(ctx)`: it reads the newest `.xplane.pb` under
`chipbench/out/trace/` (the run just wrote it; `Ctx` does not carry the
cell's name) once, keeps the `/host:CPU` events so named that start
inside the traced window, and caches them on `ctx`.  A program without
`repro.obs` opens no spans: `spans` then returns None, and so does every
metric read from them.

Times are the trace's own nanoseconds, the clock of `ctx.trace` and its
device planes.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

from trace_reduce import HOST_PLANE, TraceSummary, covered, merge

HERE = os.path.dirname(os.path.abspath(__file__))
TRACES = os.path.join(HERE, "out", "trace")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float              # ns
    end: float                # ns
    counts: Dict[str, int]    # the event's stats

    @property
    def ns(self) -> float:
        return self.end - self.start


def span_names() -> Optional[Tuple[str, ...]]:
    """Every span name the program opens, or None for a program that
    opens none."""
    try:
        from repro.obs import SPANS
    except ImportError:
        return None
    return SPANS


def newest_trace(root: str = TRACES) -> Optional[str]:
    paths = glob.glob(os.path.join(root, "*", "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def load(path: str, names: Sequence[str],
         window: Tuple[float, float]) -> List[Span]:
    """The host events of `path` named in `names` that start inside
    `window`, by start (a parent before the children it holds)."""
    from jax.profiler import ProfileData

    want, (lo, hi) = set(names), window
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in want and lo <= e.start_ns < hi:
                    start = float(e.start_ns)
                    out.append(Span(e.name, start,
                                    start + float(e.duration_ns),
                                    {k: v for k, v in e.stats}))
    out.sort(key=lambda s: (s.start, -s.end))
    return out


def spans(ctx) -> Optional[List[Span]]:
    """The program's spans in the traced window of the run `ctx`
    describes (read once), or None where there are none to read."""
    if not hasattr(ctx, "program_spans"):
        names, path = span_names(), newest_trace()
        found = None
        if ctx.trace is not None and names and path:
            found = load(path, names, ctx.trace.window) or None
        ctx.program_spans = found
    return ctx.program_spans


def named(found: Sequence[Span], name: str) -> List[Span]:
    return [s for s in found if s.name == name]


def per_run_ms(ctx, name: str) -> Optional[float]:
    """Summed length of the spans `name` per `repro.run` span (ms)."""
    found = spans(ctx)
    if not found:
        return None
    runs, parts = named(found, "repro.run"), named(found, name)
    if not runs or not parts:
        return None
    return sum(s.ns for s in parts) / len(runs) * 1e-6


def idle_unspanned(tr: TraceSummary, found: Sequence[Span]) -> List[float]:
    """Per device, the share of the window (%) in which the device ran no
    op and no program span was open."""
    lo, hi = tr.window
    opened = [(s.start, s.end) for s in found]
    return [100.0 * (1.0 - covered(merge(dev.busy + opened), lo, hi)
                     / tr.window_ns)
            for dev in tr.devices]


def idle_gaps(tr: TraceSummary, found: Sequence[Span],
              k: int = 10) -> List[Tuple[str, float]]:
    """The k longest device idle gaps in the window, each named by the
    innermost program span open at its middle ("none" where none is)."""
    by_name: Dict[str, List[Tuple[float, float]]] = {}
    for s in found:
        by_name.setdefault(s.name, []).append((s.start, s.end))
    return TraceSummary(window=tr.window, devices=tr.devices,
                        spans=by_name).idle_gaps(k)


def run_cover(found: Sequence[Span]) -> Optional[float]:
    """Share (%) of the summed length of the `repro.run` spans that the
    other program spans inside them cover."""
    runs = named(found, "repro.run")
    if not runs:
        return None
    inner = merge((s.start, s.end) for s in found if s.name != "repro.run")
    total = sum(r.ns for r in runs)
    return 100.0 * sum(covered(inner, r.start, r.end) for r in runs) / total
