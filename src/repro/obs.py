"""Host spans and process counters of the program.

A span marks one layer's host work on the profiler's trace:

    with obs.span("repro.sample", lanes=1, epochs=600):
        sched = strategy.sample_epochs(...)

It is a `jax.profiler.TraceAnnotation`: with a profiler running
(`jax.profiler.trace(dir)`) it becomes an event named `name` on the
`/host:CPU` plane, on the same clock as the device planes, with each
keyword as an integer stat of the event; with none running it costs about
a microsecond.  A count known only at the end is set on the span the
`with` yields: `sp.set_metadata(admitted=n)`.  Spans nest by the host
thread: a child lies inside its parent's interval.  They go in host code
only -- inside a function being traced (jit, scan, while body) a span
would fire once, at trace time -- and they never wait for the device.

`SPANS` names every span the program opens; readers of a trace key on it.

Counters are process-wide counts that no single call explains:
`engine_builds` (compiled engines built by the shared engine cache),
`engine_evictions` (engines the cache dropped at its cap),
`lane_buckets_split` (shape buckets the sweep engine ran as more than
one chunk, `launch.mesh.place_lanes`), and
`sample_groups_uniform` / `sample_groups_mixed` (device groups whose
epoch delays `core.delay_model.sample_epoch_totals` drew with one
success probability / with one per device).  `counters()` returns a copy.
"""
from __future__ import annotations

from typing import Dict

from jax.profiler import TraceAnnotation

SPANS = (
    "repro.plan",         # Session.plan, plan_sweep: strategy set-up
    "repro.solve",        # plan.solver.solve_redundancy_batched
    "repro.encode",       # core.encoding.encode_fleet: the parity encode
    "repro.run",          # Session.run, run_sweep
    "repro.sample",       # the host draw of every epoch's randomness
    "repro.stage",        # device operands, bucket keys, stacking
    "repro.engine",       # engine-cache lookup and the engine's dispatch
    "repro.build",        # compiling one engine (inside repro.engine)
    "repro.fetch",        # the host waiting for an engine's outputs
    "repro.report",       # TraceReport assembly
    "repro.serve.admit",  # FedServeEngine admission scan
    "repro.serve.step",   # one FedServeEngine iteration
)
_NAMES = frozenset(SPANS)

_COUNTS: Dict[str, int] = {"engine_builds": 0, "engine_evictions": 0,
                           "lane_buckets_split": 0,
                           "sample_groups_uniform": 0,
                           "sample_groups_mixed": 0}


def span(name: str, **counts: int) -> TraceAnnotation:
    """A context manager marking `name` on the profiler's host plane,
    with `counts` as the event's stats."""
    if name not in _NAMES:
        raise ValueError(f"unknown span {name!r}: add it to obs.SPANS")
    return TraceAnnotation(name, **counts)


def count(name: str, n: int = 1) -> None:
    """Add `n` to the process counter `name`."""
    _COUNTS[name] += n


def counters() -> Dict[str, int]:
    """A copy of the process counters."""
    return dict(_COUNTS)
