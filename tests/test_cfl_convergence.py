"""Integration tests: the full CFL protocol + wall-clock simulation converge
and reproduce the paper's qualitative claims (scaled down for CI speed)."""
import jax
import numpy as np
import pytest

from repro.api import CodedFL, Session, TrainData, UncodedFL, coding_gain
from repro.core import cfl
from repro.sim.network import paper_fleet


@pytest.fixture(scope="module")
def small_problem():
    fleet = paper_fleet(0.2, 0.2, seed=1, n=12, d=60)
    data = TrainData.linreg(jax.random.PRNGKey(0), n=12, ell=80, d=60)
    return fleet, data


def run_uncoded(fleet, data, lr, epochs, rng):
    return Session(strategy=UncodedFL(), fleet=fleet, lr=lr,
                   epochs=epochs).run(data, rng=rng)


def run_cfl(fleet, data, lr, epochs, rng, **strategy):
    return Session(strategy=CodedFL(**strategy), fleet=fleet, lr=lr,
                   epochs=epochs).run(data, rng=rng)


def test_uncoded_converges(small_problem):
    fleet, data = small_problem
    res = run_uncoded(fleet, data, lr=0.05, epochs=250,
                      rng=np.random.default_rng(0))
    assert res.final_nmse() < 1e-2
    assert np.all(np.diff(res.times) > 0)


def test_cfl_converges_and_is_faster(small_problem):
    fleet, data = small_problem
    m = data.m
    res_u = run_uncoded(fleet, data, lr=0.05, epochs=250,
                        rng=np.random.default_rng(0))
    res_c = run_cfl(fleet, data, lr=0.05, epochs=250,
                    rng=np.random.default_rng(0), key=jax.random.PRNGKey(1),
                    fixed_c=int(0.3 * m), include_upload_delay=False)
    assert res_c.final_nmse() < 2e-2
    tgt = 1e-1
    g = coding_gain(res_u, res_c, tgt)
    assert g > 1.0, f"coding gain {g} should exceed 1 under heterogeneity"


def test_cfl_epoch_deadline_is_tstar(small_problem):
    fleet, data = small_problem
    m = data.m
    res_c = run_cfl(fleet, data, lr=0.05, epochs=5,
                    rng=np.random.default_rng(2), key=jax.random.PRNGKey(1),
                    fixed_c=int(0.2 * m), include_upload_delay=False)
    # all CFL epochs take exactly t*: the tail is clipped (paper Fig. 3)
    assert np.allclose(res_c.epoch_durations, res_c.epoch_durations[0])


def test_uncoded_epochs_have_straggler_tail(small_problem):
    fleet, data = small_problem
    res_u = run_uncoded(fleet, data, lr=0.05, epochs=60,
                        rng=np.random.default_rng(3))
    durs = res_u.epoch_durations
    assert durs.max() > 1.25 * np.median(durs), "expected a straggler tail"


def test_upload_delay_accounting(small_problem):
    fleet, data = small_problem
    m = data.m
    kw = dict(lr=0.05, epochs=3, key=jax.random.PRNGKey(1),
              fixed_c=int(0.2 * m))
    with_up = run_cfl(fleet, data, rng=np.random.default_rng(4),
                      include_upload_delay=True, **kw)
    without = run_cfl(fleet, data, rng=np.random.default_rng(4),
                      include_upload_delay=False, **kw)
    assert with_up.setup_time > 0
    assert with_up.times[0] == pytest.approx(with_up.setup_time)
    assert without.times[0] == 0.0
    # uplink accounting includes the one-time parity shipment
    assert with_up.uplink_bits_total > 3 * 12 * 2 * fleet.packet_bits


def test_delta_zero_degenerates_to_deadline_uncoded(small_problem):
    fleet, data = small_problem
    res = run_cfl(fleet, data, lr=0.05, epochs=3,
                  rng=np.random.default_rng(5), key=jax.random.PRNGKey(1),
                  fixed_c=0, include_upload_delay=True)
    assert res.setup_time == 0.0
    assert res.final_nmse() < 1.0  # still makes progress from received grads


def test_setup_state_consistency(small_problem):
    fleet, data = small_problem
    m = data.m
    state = cfl.setup(jax.random.PRNGKey(0), data.xs, data.ys, fleet.edge,
                      fleet.server, fixed_c=int(0.25 * m))
    assert state.c == int(0.25 * m)
    assert state.x_parity.shape == (state.c, data.d)
    # load mask rows sum to the plan loads
    np.testing.assert_array_equal(
        np.asarray(state.load_mask.sum(axis=1), dtype=np.int64),
        state.plan.loads)
    # weights: processed points carry sqrt(1-p_i) <= 1, punctured exactly 1
    w = np.asarray(state.weights)
    lm = np.asarray(state.load_mask).astype(bool)
    assert np.all(w[~lm] == 1.0)
    assert np.all(w[lm] <= 1.0)
