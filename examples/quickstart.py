"""Quickstart: Coded Federated Learning end-to-end in ~30 seconds.

Builds the paper's §IV setup (24 heterogeneous edge devices, linear
regression, d=500), runs the two-step redundancy optimization, trains with
CFL vs uncoded FL through the unified Strategy/Session API (one scan-jitted
epoch engine for both), and prints the coding gain.

    PYTHONPATH=src python examples/quickstart.py [--epochs 600]
"""
import argparse

import jax
import numpy as np

from repro.api import (CodedFL, Session, TrainData, UncodedFL, coding_gain,
                       convergence_time)
from repro.core.redundancy import solve_redundancy
from repro.launch.compile_cache import use_compile_cache
from repro.sim.network import paper_fleet

N, ELL, D = 24, 300, 500
M = N * ELL
LR = 0.0085
TARGET = 1e-3


def main(epochs: int = 600):
    print("=== Coded Federated Learning quickstart ===")
    fleet = paper_fleet(nu_comp=0.2, nu_link=0.2, seed=0)
    data = TrainData.linreg(jax.random.PRNGKey(0), N, ELL, D)

    # Step 1-2: redundancy optimization (Eqs. 14-16)
    plan = solve_redundancy(fleet.edge, fleet.server, np.full(N, ELL),
                            fixed_c=int(0.28 * M))
    print(f"plan: c={plan.c} (delta={plan.delta:.2f}) t*={plan.t_star:.2f}s")
    print(f"per-device loads: {plan.loads.tolist()}")

    # baseline: synchronous uncoded FL (wait for every straggler)
    res_u = Session(strategy=UncodedFL(), fleet=fleet, lr=LR,
                    epochs=epochs).run(data, rng=np.random.default_rng(0))
    # CFL: parity upload once, then deadline-clipped epochs
    res_c = Session(strategy=CodedFL(key=jax.random.PRNGKey(1),
                                     fixed_c=plan.c,
                                     include_upload_delay=False),
                    fleet=fleet, lr=LR,
                    epochs=epochs).run(data, rng=np.random.default_rng(0))

    print(f"\nuncoded: NMSE {res_u.final_nmse():.2e} after "
          f"{res_u.times[-1]:.0f}s simulated")
    print(f"coded:   NMSE {res_c.final_nmse():.2e} after "
          f"{res_c.times[-1]:.0f}s simulated "
          f"(epoch deadline {plan.t_star:.1f}s)")
    g = coding_gain(res_u, res_c, TARGET)
    print(f"\ncoding gain to NMSE<={TARGET}: {g:.2f}x "
          f"(uncoded {convergence_time(res_u, TARGET):.0f}s vs "
          f"coded {convergence_time(res_c, TARGET):.0f}s)")


if __name__ == "__main__":
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=600,
                    help="training epochs (30 for a CI smoke run)")
    main(**vars(ap.parse_args()))
