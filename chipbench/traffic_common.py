"""What every closed-loop driver shares: the count of the epochs it ran.

Each call records `sessions`: (strategy name, delay-generator seed, the
program's plan or None).  The count takes each session's arrival masks
from the reference sampler with that seed, so it is drawn from the
problem and never from the program's layout (`count.py`).
"""
from __future__ import annotations

import numpy as np

import count
from reference import cfl as ref


def work(ctx, calls) -> count.Work:
    """The count of the epochs these calls ran, from the masks the
    reference sampler draws with each session's own generator."""
    system = ctx.system
    d = system.cfg["data"]["d"]
    epochs = system.cfg["epochs"]
    total = count.NONE
    for c in calls:
        for name, rng, plan in c["sessions"]:
            gen = np.random.default_rng(rng)
            if getattr(plan, "c", 0) > 0:
                p = ref.Plan(np.asarray(plan.loads), int(plan.c),
                             float(plan.t_star), np.asarray(plan.p_return),
                             float("nan"))
                s = ref.sample_coded(system.ref_fleet, p, d, epochs, gen)
                rows = count.masked_rows(p.loads, s.received)
                total = total + count.epoch_work(d, rows, p.c, s.parity_ok)
            else:
                rows = np.full(epochs, float(system.sizes.sum()))
                total = total + count.epoch_work(d, rows)
    return total
