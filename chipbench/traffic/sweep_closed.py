"""Closed loop of sweeps: `plan_sweep` then `run_sweep` over many lanes.

Traffic parameters (the traffic file):
  strategy      the configuration's strategy name every lane runs
  fleet_seed    the seed the fleet is drawn from under every `--seed`:
                a sweep plans one fleet, and the fleet sets each lane's
                loads and so the shape buckets its lanes fall into
  fixed_c       one parity budget per lane (the sweep's deltas times m)
  lanes         how many lanes a call has (a failed call counts them all)
  warm_rounds   sweeps run in set-up (the first compiles every bucket)
  check         {"blocks": b, "within_first": j}: one of the first j
                sweeps, drawn from the seed, has one lane compared in each
                of b equal blocks of its lanes (the lane mesh splits each
                shape bucket's lanes over devices in contiguous blocks)
  trace_seconds how long a `--trace 1` run traces

Each sweep draws a new key and delay generator per lane from `--seed`, at
the same shapes, so nothing compiles in the window; the data, too, comes
from `--seed`.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from deploy import derive, strategy
from traffic_common import work  # noqa: F401  (the count of the epochs)


def _sweep(ctx, keys, rngs):
    from repro.api import Session, plan_sweep, run_sweep

    cfg, t = ctx.system.cfg, ctx.traffic
    sessions = [Session(strategy=strategy(ctx.system, t["strategy"], k,
                                          fixed_c=c),
                        fleet=ctx.system.fleet, lr=cfg["lr"],
                        epochs=cfg["epochs"])
                for k, c in zip(keys, t["fixed_c"])]
    with ctx.span("session"):
        with ctx.span("plan", coded=True):
            states = plan_sweep(sessions, ctx.system.data)
            jax.block_until_ready([v for s in states
                                   for v in vars(s).values()
                                   if isinstance(v, jax.Array)])
        with ctx.span("run"):
            reports = run_sweep(sessions, ctx.system.data,
                                rngs=[np.random.default_rng(r)
                                      for r in rngs], states=states)
    return states, reports


def pick(ctx):
    """(sweep index, lane indices) whose answers are compared: lanes are
    split over devices in contiguous blocks within each shape bucket, so
    one lane is drawn from each block of every bucket."""
    spec, lanes = ctx.traffic["check"], len(ctx.traffic["fixed_c"])
    gen = np.random.default_rng(derive(ctx.seed, 5))
    i = int(gen.integers(spec["within_first"]))
    blocks = np.array_split(np.arange(lanes), spec["blocks"])
    return i, {int(gen.choice(b)) for b in blocks}


def warm(ctx) -> None:
    ctx.keep = pick(ctx)
    ctx.answers = []
    lanes = len(ctx.traffic["fixed_c"])
    for r in range(ctx.traffic["warm_rounds"]):
        _sweep(ctx, [derive(ctx.seed, 4, r, j) for j in range(lanes)],
               [derive(ctx.seed, 6, r, j) for j in range(lanes)])


def _ids(ctx, i: int):
    """Generator keys and delay seeds of sweep i's lanes."""
    lanes = range(len(ctx.traffic["fixed_c"]))
    return ([derive(ctx.seed, 2, i, j) for j in lanes],
            [derive(ctx.seed, 3, i, j) for j in lanes])


def compared(ctx):
    """(name, key, rng, overrides) of every lane a run compares."""
    i, lanes = pick(ctx)
    keys, rngs = _ids(ctx, i)
    t = ctx.traffic
    return [(t["strategy"], keys[j], rngs[j], {"fixed_c": t["fixed_c"][j]})
            for j in sorted(lanes)]


def call(ctx, i: int) -> dict:
    """Issue sweep i and wait for every lane's report."""
    t = ctx.traffic
    lanes = len(t["fixed_c"])
    keys, rngs = _ids(ctx, i)
    issued = time.perf_counter()
    states, reports = _sweep(ctx, keys, rngs)
    done = time.perf_counter()
    bad = sum(not np.all(np.isfinite(r.nmse)) for r in reports)
    if i == ctx.keep[0]:
        for j in sorted(ctx.keep[1]):  # copied once the window has closed
            ctx.answers.append((t["strategy"], keys[j], rngs[j],
                                {"fixed_c": t["fixed_c"][j]}, states[j],
                                reports[j]))
    return {"issued": issued, "done": done, "lanes": lanes,
            "epochs": ctx.system.cfg["epochs"], "failed": int(bad),
            "sessions": [(t["strategy"], r, s.plan)
                         for r, s in zip(rngs, states)]}
