"""What every closed-loop driver shares: the count of the epochs it ran.

Each call records `sessions`: (strategy name, delay-generator seed, the
program's plan or None).  The configuration's reference module counts
each session over the arrival masks its own sampler draws with that seed
(the ones it compares the session against), so the count is drawn from
the problem and never from the program's layout (`count.py`).
"""
from __future__ import annotations

import count


def work(ctx, calls) -> count.Work:
    """The count of the epochs these calls ran."""
    system = ctx.system
    total = count.NONE
    for c in calls:
        for name, rng, plan in c["sessions"]:
            total = total + system.reference.work(system, name, rng, plan)
    return total
