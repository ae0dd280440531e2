"""Tests for the gradient-coding baseline (paper ref [5] comparator)."""
import _oracle as oracle
import jax
import numpy as np
import pytest

from repro.api import GradientCodingFL, Session, TrainData
from repro.core import gradient_coding as GC
from repro.core.delay_model import sample_total
from repro.sim.network import paper_fleet


def group_gradients(xs, ys, beta, plan):
    """Each group's exact gradient over all its members' data:
    (n_groups, d)."""
    per_client = oracle.client_gradients(xs, ys, beta)         # (n, d)
    n_groups = int(plan.groups.max()) + 1
    onehot = np.arange(n_groups)[None, :] == plan.groups[:, None]
    return onehot.T.astype(np.float64) @ per_client


def epoch_time(fleet, plan, ell, rng):
    """Wall time until every group has >= 1 returner: each client
    processes r*ell points (its whole group's data), and the epoch ends
    at max over groups of (min over group members)."""
    loads = np.full(fleet.edge.n, plan.r * ell)
    t_i = sample_total(fleet.edge, loads, rng)
    n_groups = int(plan.groups.max()) + 1
    per_group = np.full(n_groups, np.inf)
    for i, g in enumerate(plan.groups):
        per_group[g] = min(per_group[g], t_i[i])
    return float(per_group.max())


def test_make_plan_groups():
    plan = GC.make_plan(12, 3)
    assert plan.r == 3
    assert len(plan.groups) == 12
    _, counts = np.unique(plan.groups, return_counts=True)
    assert np.all(counts == 3)
    assert plan.tolerated_stragglers_per_group == 2


def test_make_plan_rejects_non_divisor():
    with pytest.raises(ValueError):
        GC.make_plan(10, 3)


def test_group_gradients_partition_full_gradient():
    data = TrainData.linreg(jax.random.PRNGKey(0), n=8, ell=10, d=6)
    plan = GC.make_plan(8, 2)
    beta = jax.random.normal(jax.random.PRNGKey(1), (6,))
    gg = group_gradients(data.xs, data.ys, beta, plan)
    full = oracle.full_gradient(data.xs, data.ys, beta)
    np.testing.assert_allclose(gg.sum(axis=0), full, rtol=1e-4, atol=1e-4)


def test_epoch_time_decreases_with_replication():
    """More replication => min-over-group-members => shorter group waits,
    but each member computes r x more; with compute-dominated delays the
    net can go either way — assert only that the mechanics hold: r=1
    equals the uncoded max, and all times are positive/finite."""
    fleet = paper_fleet(0.2, 0.2, seed=0, n=12, d=50)
    rng = np.random.default_rng(0)
    t1 = [epoch_time(fleet, GC.make_plan(12, 1), 50, rng)
          for _ in range(50)]
    t3 = [epoch_time(fleet, GC.make_plan(12, 3), 50, rng)
          for _ in range(50)]
    assert all(np.isfinite(t1)) and all(np.isfinite(t3))
    assert min(t1 + t3) > 0


def test_vectorized_sample_epochs_matches_legacy_loop():
    """Satellite regression: the `np.minimum.at` group reduction in
    `GradientCodingFL.sample_epochs` reproduces the seed's per-client
    Python loop trace-identically (same generator draws, same epoch
    durations, bit for bit)."""
    fleet = paper_fleet(0.2, 0.2, seed=0, n=12, d=50)
    data = TrainData.linreg(jax.random.PRNGKey(0), n=12, ell=30, d=50)
    strat = GradientCodingFL(r=3)
    state = strat.plan(fleet, data)
    epochs = 40

    sched = strat.sample_epochs(state, fleet, epochs,
                                np.random.default_rng(7))

    # the seed's loop, verbatim (per-epoch sampling + per-client min scan)
    rng = np.random.default_rng(7)
    loads = np.full(fleet.edge.n, state.plan.r * state.ell)
    legacy = np.empty(epochs)
    for e in range(epochs):
        t_i = sample_total(fleet.edge, loads, rng)
        per_group = np.full(state.n_groups, np.inf)
        for i, g in enumerate(state.plan.groups):
            per_group[g] = min(per_group[g], t_i[i])
        legacy[e] = float(per_group.max())

    np.testing.assert_array_equal(sched.durations, legacy)
    assert sched.arrivals["group_ok"].shape == (epochs, state.n_groups)
    assert np.all(sched.arrivals["group_ok"] == 1.0)


@pytest.mark.parametrize("r", [1, 3])
@pytest.mark.parametrize("fleet_kind", ["paper", "wireless"])
def test_sample_epochs_matches_frozen_loop(fleet_kind, r):
    """`GradientCodingFL.sample_epochs` (and `sweep_inputs`) against a
    frozen copy of its per-epoch sampling loop: the same schedule bit for
    bit, and the generator left where the loop leaves it."""
    from repro.sim.network import wireless_fleet

    if fleet_kind == "paper":
        fleet = paper_fleet(0.2, 0.2, seed=4, n=12, d=50)
    else:
        fleet = wireless_fleet(0.2, 0.2, nu_erasure=0.3, seed=4, n=12, d=50)
    data = TrainData.linreg(jax.random.PRNGKey(0), n=12, ell=30, d=50)
    strat = GradientCodingFL(r=r)
    state = strat.plan(fleet, data)
    epochs = 120
    seed = 2**31 + 3

    rng_old = np.random.default_rng(seed)
    n = fleet.edge.n
    loads = np.full(n, state.plan.r * state.ell)
    t_all = np.empty((epochs, n))
    for e in range(epochs):
        t_all[e] = sample_total(fleet.edge, loads, rng_old)
    groups = np.asarray(state.plan.groups)
    per_group = np.full((epochs, state.n_groups), np.inf)
    np.minimum.at(per_group,
                  (np.arange(epochs)[:, None], groups[None, :]), t_all)
    durations = per_group.max(axis=1)

    for draw in (strat.sample_epochs, strat.sweep_inputs):
        rng = np.random.default_rng(seed)
        sched = draw(state, fleet, epochs, rng)
        np.testing.assert_array_equal(sched.durations, durations)
        np.testing.assert_array_equal(
            sched.arrivals["group_ok"],
            np.ones((epochs, state.n_groups), np.float32))
        assert sched.setup_time == sched.t0 == state.shard_time
        assert rng.bit_generator.state == rng_old.bit_generator.state


def test_gradient_coding_converges():
    fleet = paper_fleet(0.2, 0.2, seed=1, n=12, d=60)
    data = TrainData.linreg(jax.random.PRNGKey(0), n=12, ell=80, d=60)
    res = Session(strategy=GradientCodingFL(r=3), fleet=fleet, lr=0.05,
                  epochs=200).run(data, rng=np.random.default_rng(0))
    assert res.final_nmse() < 1e-2
    assert res.setup_time > 0  # raw-data sharing cost is accounted
