"""Closed loop of solo sessions, one at a time, as a user runs them.

Traffic parameters (the traffic file):
  mix           the configuration's strategy names, taken in turn
  warm_rounds   rounds of the mix run in set-up (the first compiles)
  check         {strategy name: answers to compare, "within_first": k}:
                which sessions `correct` is decided on, drawn from the
                seed among the first k of the window
  trace_seconds how long a `--trace 1` run traces

Each session gets its own generator key and delay generator, drawn from
`--seed` and its index, at the same shapes, so nothing compiles in the
window.  A session is `Session.plan(data)`, ended by
`block_until_ready` on the state's arrays, then
`Session.run(data, rng, state=...)`; it is timed from its issue to its
`TraceReport` in hand.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from deploy import derive, strategy
from traffic_common import work  # noqa: F401  (the count of the epochs)


def _session(ctx, name: str, key: int, rng: int):
    from repro.api import Session

    cfg = ctx.system.cfg
    sess = Session(strategy=strategy(ctx.system, name, key),
                   fleet=ctx.system.fleet, lr=cfg["lr"],
                   epochs=cfg["epochs"])
    coded = bool(cfg["strategies"][name].get("keyed", False))
    with ctx.span("session"):
        with ctx.span("plan", coded=coded):
            state = sess.plan(ctx.system.data)
            jax.block_until_ready([v for v in vars(state).values()
                                   if isinstance(v, jax.Array)])
        with ctx.span("run"):
            report = sess.run(ctx.system.data,
                              rng=np.random.default_rng(rng), state=state)
    return state, report


def pick(ctx):
    """Indices of the window's sessions whose answers are compared."""
    mix, spec = ctx.traffic["mix"], dict(ctx.traffic["check"])
    first = spec.pop("within_first")
    gen = np.random.default_rng(derive(ctx.seed, 5))
    out = set()
    for name, k in sorted(spec.items()):
        cand = [i for i in range(first) if mix[i % len(mix)] == name]
        out.update(int(i) for i in gen.choice(cand, size=k, replace=False))
    return out


def warm(ctx) -> None:
    ctx.keep = pick(ctx)
    ctx.answers = []
    for r in range(ctx.traffic["warm_rounds"]):
        for j, name in enumerate(ctx.traffic["mix"]):
            _session(ctx, name, derive(ctx.seed, 4, r, j),
                     derive(ctx.seed, 6, r, j))


def ident(ctx, i: int):
    """(strategy name, generator key, delay seed) of session i."""
    mix = ctx.traffic["mix"]
    return mix[i % len(mix)], derive(ctx.seed, 2, i), derive(ctx.seed, 3, i)


def compared(ctx):
    """(name, key, rng, overrides) of every session a run compares."""
    return [(*ident(ctx, i), {}) for i in sorted(pick(ctx))]


def call(ctx, i: int) -> dict:
    """Issue session i and wait for its report."""
    name, key, rng = ident(ctx, i)
    issued = time.perf_counter()
    state, report = _session(ctx, name, key, rng)
    done = time.perf_counter()
    ok = bool(np.all(np.isfinite(report.nmse)))
    if i in ctx.keep:  # copied to the host once the window has closed
        ctx.answers.append((name, key, rng, {}, state, report))
    plan = getattr(state, "plan", None)
    return {"issued": issued, "done": done, "lanes": 1,
            "epochs": ctx.system.cfg["epochs"], "failed": 0 if ok else 1,
            "sessions": [(name, rng, plan)]}
