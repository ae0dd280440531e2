"""Micro-benchmark: planning + training engines, new vs the seed's stack.

Planning (`CodedFL.plan` = redundancy solve + parity encoding) used to
dominate Session wall time: ~4s on the §IV config vs ~0.3s for the whole
scan-jitted training trace.  Two sections quantify the replacement:

  * plan_single — one §IV fixed-c plan: the seed's scalar stack (bisection
    with one CDF call per integer load, `repro.plan.reference`, plus the
    stack-then-sum encoder) vs the batched grid solver + streamed encoder.
  * plan_sweep16 — a 16-point fixed-c sweep planned in ONE
    `solve_redundancy_batched` call vs 16 sequential legacy solves
    (legacy cost = 16x the measured single solve).

The training section times the scan-jitted `Session` engine on the
planned state.

    PYTHONPATH=src python -m benchmarks.perf_session [--epochs 300]
    PYTHONPATH=src python -m benchmarks.perf_session --smoke   # CI budget

`--smoke` runs only the new planner (no multi-second legacy baselines) and
asserts plan latencies stay under fixed budgets, so planner regressions
fail CI instead of silently eating sweep time.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import CodedFL, Session, TrainData
from repro.core import cfl
from repro.core.encoding import generator_matrix
from repro.plan import PlanRequest, solve_redundancy_batched
from repro.sim.network import paper_fleet

from .common import D, ELL, LR, M, N_DEVICES, dump_bench, emit

# --smoke budgets (seconds, warm): generous multiples of the measured warm
# latencies (~0.1s single / ~1.8s sweep on the dev box) so CI noise does not
# flake, while a return of the 4s-per-plan stack still fails loudly.
SMOKE_SINGLE_BUDGET_S = 1.0
SMOKE_SWEEP_BUDGET_S = 5.0


def legacy_encode_fleet(key, xs, ys, weights, c):
    """The seed's stack-then-sum fleet encoder (kept here as baseline)."""
    n = xs.shape[0]
    keys = jax.random.split(key, n)

    def one(k, x, y, w):
        g = generator_matrix(k, c, x.shape[0], dtype=x.dtype)
        return g @ (w[:, None] * x), g @ (w * y)

    xps, yps = jax.vmap(one)(keys, xs, ys, weights)
    return jnp.sum(xps, axis=0), jnp.sum(yps, axis=0)


def bench_planning(fleet, data: TrainData, session: Session, c: int,
                   smoke: bool) -> cfl.CFLState:
    """Plan-latency section; returns the planned state for the train bench."""
    sizes = np.full(N_DEVICES, ELL, dtype=np.int64)
    req = PlanRequest(edge=fleet.edge, server=fleet.server, data_sizes=sizes,
                      fixed_c=c)
    sweep_reqs = [PlanRequest(edge=fleet.edge, server=fleet.server,
                              data_sizes=sizes, fixed_c=int(delta * M))
                  for delta in np.linspace(0.05, 0.5, 16)]

    # warm up the jitted solver + encoder for both batch shapes
    solve_redundancy_batched([req])
    solve_redundancy_batched(sweep_reqs)
    state = session.plan(data)
    jax.block_until_ready(state.x_parity)

    t0 = time.perf_counter()
    solve_redundancy_batched([req])
    t_solve = time.perf_counter() - t0

    t0 = time.perf_counter()
    state = session.plan(data)
    jax.block_until_ready(state.x_parity)
    t_plan = time.perf_counter() - t0

    t0 = time.perf_counter()
    sweep_plans = solve_redundancy_batched(sweep_reqs)
    t_sweep = time.perf_counter() - t0
    assert len(sweep_plans) == 16 and all(p.c > 0 for p in sweep_plans)

    if smoke:
        emit("perf_session/plan_single_new", t_plan * 1e6,
             f"solve={t_solve*1e3:.0f}ms;budget={SMOKE_SINGLE_BUDGET_S}s")
        emit("perf_session/plan_sweep16_new", t_sweep * 1e6,
             f"budget={SMOKE_SWEEP_BUDGET_S}s")
        # artifact FIRST: a budget regression is exactly when the measured
        # values must survive into the uploaded BENCH_perf.json
        dump_bench("perf", gates={
            "plan_single_s": round(t_plan, 4),
            "plan_single_budget_s": SMOKE_SINGLE_BUDGET_S,
            "plan_solve_s": round(t_solve, 4),
            "plan_sweep16_s": round(t_sweep, 4),
            "plan_sweep16_budget_s": SMOKE_SWEEP_BUDGET_S,
        })
        assert t_plan < SMOKE_SINGLE_BUDGET_S, \
            f"single plan {t_plan:.2f}s over budget {SMOKE_SINGLE_BUDGET_S}s"
        assert t_sweep < SMOKE_SWEEP_BUDGET_S, \
            f"16-pt sweep {t_sweep:.2f}s over budget {SMOKE_SWEEP_BUDGET_S}s"
        return state

    # --- legacy baselines: the seed's scalar solve + stack-then-sum encode
    from repro.plan.reference import solve_redundancy_reference
    t0 = time.perf_counter()
    plan_ref = solve_redundancy_reference(fleet.edge, fleet.server, sizes,
                                          fixed_c=c)
    t_solve_ref = time.perf_counter() - t0

    from repro.core.redundancy import systematic_weights
    w_ref = jnp.asarray(np.stack(systematic_weights(plan_ref, sizes)),
                        dtype=data.xs.dtype)
    legacy_encode_fleet(session.strategy.key, data.xs, data.ys, w_ref, c)
    t0 = time.perf_counter()
    xp, _ = legacy_encode_fleet(session.strategy.key, data.xs, data.ys,
                                w_ref, c)
    jax.block_until_ready(xp)
    t_enc_ref = time.perf_counter() - t0
    t_plan_ref = t_solve_ref + t_enc_ref

    # sanity: the shimmed plan matches the seed algorithm.  At the default
    # eps_rel=1e-3 both solvers stop within tolerance of the true crossing
    # but at slightly different deadlines, so an integer load may shift by
    # one point; the strict identical-loads parity is enforced at tighter
    # eps in tests/test_plan_solver.py.
    plan_new = state.plan
    np.testing.assert_allclose(plan_new.t_star, plan_ref.t_star, rtol=1e-3)
    assert np.max(np.abs(plan_new.loads - plan_ref.loads)) <= 1
    assert plan_new.c == plan_ref.c

    emit("perf_session/plan_single", t_plan * 1e6,
         f"legacy={t_plan_ref:.2f}s(solve={t_solve_ref:.2f}+"
         f"enc={t_enc_ref:.2f});new={t_plan:.2f}s;"
         f"speedup={t_plan_ref / t_plan:.1f}x")
    emit("perf_session/plan_sweep16", t_sweep * 1e6,
         f"legacy_est={16 * t_solve_ref:.1f}s(16 solves);"
         f"new_batched={t_sweep:.2f}s;"
         f"speedup={16 * t_solve_ref / t_sweep:.1f}x")
    print(f"plan: legacy {t_plan_ref:.2f}s -> new {t_plan:.2f}s "
          f"({t_plan_ref / t_plan:.1f}x) | 16-pt sweep: "
          f"{16 * t_solve_ref:.1f}s -> {t_sweep:.2f}s "
          f"({16 * t_solve_ref / t_sweep:.1f}x, one batched call)")
    return state


def main(epochs: int = 300, delta: float = 0.28,
         smoke: bool = False) -> None:
    fleet = paper_fleet(0.2, 0.2, seed=0)
    data = TrainData.linreg(jax.random.PRNGKey(0), N_DEVICES, ELL, D)
    c = int(delta * M)

    session = Session(strategy=CodedFL(key=jax.random.PRNGKey(1), fixed_c=c,
                                       include_upload_delay=False),
                      fleet=fleet, lr=LR, epochs=epochs)

    # --- planning section --------------------------------------------------
    state = bench_planning(fleet, data, session, c, smoke)
    if smoke:
        print("perf_session --smoke OK (plan budgets held)")
        return

    # --- training engine (planned state shared) ----------------------------
    session.run(data, rng=np.random.default_rng(0), state=state)  # compile
    t0 = time.perf_counter()
    session.run(data, rng=np.random.default_rng(1), state=state)
    t_scan = time.perf_counter() - t0
    eps_scan = epochs / t_scan
    emit("perf_session/scan_jitted", t_scan * 1e6 / epochs,
         f"epochs_per_sec={eps_scan:.0f};epochs={epochs};n={N_DEVICES};"
         f"d={D}")
    print(f"\nscan-jitted Session: {eps_scan:.0f} epochs/s")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=300)
    ap.add_argument("--delta", type=float, default=0.28)
    ap.add_argument("--smoke", action="store_true",
                    help="quick CI mode: new planner only, assert budgets")
    main(**vars(ap.parse_args()))
