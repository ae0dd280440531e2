"""Tests for the unified Strategy/Session API (repro.api).

The load-bearing guarantee: `Session`'s single scan-jitted epoch engine
reproduces the legacy per-epoch Python loops EXACTLY — same NumPy generator
draw order, same arrival masks, same fp32 gradient arithmetic — so the
legacy reference loops are reimplemented here (from the seed code) and the
new engine is checked against them trace-for-trace.
"""
import _oracle as oracle
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis, or a deterministic fallback

from repro.api import (CodedFL, EpochSchedule, GradientCodingFL, Session,
                       TrainData, UncodedFL, coding_gain, convergence_time)
from repro.core import aggregation, cfl
from repro.core.delay_model import sample_total
from repro.sim.network import paper_fleet, wireless_fleet


@pytest.fixture(scope="module")
def small():
    fleet = paper_fleet(0.2, 0.2, seed=1, n=12, d=60)
    data = TrainData.linreg(jax.random.PRNGKey(0), n=12, ell=80, d=60)
    return fleet, data


# ---------------------------------------------------------------------------
# legacy reference loops (per-epoch Python, host-synced — the seed code)
# ---------------------------------------------------------------------------

def _legacy_run_uncoded(fleet, data, lr, epochs, rng):
    xs, ys, beta_true = data.xs, data.ys, data.beta_true
    n, ell, d = xs.shape
    m = n * ell
    x, y = xs.reshape(m, d), ys.reshape(m)
    beta = jnp.zeros(d, dtype=xs.dtype)
    full_load = np.full(n, ell)
    errs = [float(aggregation.nmse(beta, beta_true))]
    durs = []
    for _ in range(epochs):
        t_i = sample_total(fleet.edge, full_load, rng)
        durs.append(float(np.max(t_i)))
        g = (x @ beta - y) @ x
        beta = aggregation.gd_update(beta, g, lr, m)
        errs.append(float(aggregation.nmse(beta, beta_true)))
    return np.array(errs), np.array(durs)


def _legacy_run_cfl(fleet, data, lr, epochs, rng, key, fixed_c,
                    server_always_returns=False):
    xs, ys, beta_true = data.xs, data.ys, data.beta_true
    n, ell, d = xs.shape
    m = n * ell
    state = cfl.setup(key, xs, ys, fleet.edge, fleet.server, fixed_c=fixed_c)
    plan = state.plan
    t_star = plan.t_star

    upload_bits = state.parity_upload_bits()
    packets = np.ceil(upload_bits / fleet.packet_bits)
    retrans = rng.geometric(1.0 - fleet.edge.p, size=n)
    upload_time = float(np.max(packets * retrans
                               * (fleet.packet_bits / fleet.link_rates))) \
        if state.c > 0 else 0.0

    beta = jnp.zeros(d, dtype=xs.dtype)
    errs = [float(aggregation.nmse(beta, beta_true))]
    for _ in range(epochs):
        t_i = sample_total(fleet.edge, plan.loads, rng)
        received = jnp.asarray((t_i <= t_star) & (plan.loads > 0),
                               dtype=xs.dtype)
        if server_always_returns or state.c == 0:
            par_ok = jnp.asarray(1.0, dtype=xs.dtype)
        else:
            t_srv = sample_total(fleet.server, np.array([state.c]), rng)[0]
            par_ok = jnp.asarray(float(t_srv <= t_star), dtype=xs.dtype)
        # systematic partials over each client's load, summed over the
        # clients whose partial arrived by t*, plus the Eq.-18 parity term
        resid = (jnp.einsum("nld,d->nl", xs, beta) - ys) * state.load_mask
        partials = jnp.einsum("nld,nl->nd", xs, resid)
        g = jnp.einsum("nd,n->d", partials, received)
        if state.c > 0:
            g_par = (state.x_parity @ beta - state.y_parity) \
                @ state.x_parity / state.c
            g = g + par_ok * g_par
        beta = aggregation.gd_update(beta, g, lr, m)
        errs.append(float(aggregation.nmse(beta, beta_true)))
    return np.array(errs), upload_time, t_star


def _legacy_run_gradcoding(fleet, data, lr, epochs, rng, r):
    """Fractional-repetition gradient coding as a per-epoch loop: each
    client computes its group's r shards, an epoch ends when every group
    has a returner, and the decoded gradient is the full one."""
    xs, ys, beta_true = data.xs, data.ys, data.beta_true
    n, ell, d = xs.shape
    m = n * ell
    x, y = xs.reshape(m, d), ys.reshape(m)
    groups = np.repeat(np.arange(n // r), r)
    share_bits = (r - 1) * ell * (d + 1) * 32 * 1.1
    setup = float(np.max(share_bits / fleet.link_rates))
    loads = np.full(n, r * ell)
    beta = jnp.zeros(d, dtype=xs.dtype)
    errs = [float(aggregation.nmse(beta, beta_true))]
    durs = []
    for _ in range(epochs):
        t_i = sample_total(fleet.edge, loads, rng)
        per_group = np.full(n // r, np.inf)
        for i, g in enumerate(groups):
            per_group[g] = min(per_group[g], t_i[i])
        durs.append(float(per_group.max()))
        g = (x @ beta - y) @ x
        beta = aggregation.gd_update(beta, g, lr, m)
        errs.append(float(aggregation.nmse(beta, beta_true)))
    return np.array(errs), np.array(durs), setup


# ---------------------------------------------------------------------------
# trace parity: scan-jitted Session == legacy per-epoch loop
# ---------------------------------------------------------------------------

def test_uncoded_session_matches_legacy_trace(small):
    fleet, data = small
    errs, durs = _legacy_run_uncoded(fleet, data, lr=0.05, epochs=100,
                                     rng=np.random.default_rng(0))
    session = Session(strategy=UncodedFL(), fleet=fleet, lr=0.05, epochs=100)
    rep = session.run(data, rng=np.random.default_rng(0))
    np.testing.assert_allclose(rep.nmse, errs, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(rep.epoch_durations, durs)  # identical draws
    np.testing.assert_allclose(rep.times[1:], np.cumsum(durs))


def test_cfl_session_matches_legacy_trace(small):
    """The fused Session against the per-epoch loop: the packed rows and
    the Gram-folded parity reassociate float sums, hence rtol 1e-3."""
    fleet, data = small
    c = int(0.3 * data.m)
    errs, upload, t_star = _legacy_run_cfl(
        fleet, data, lr=0.05, epochs=100, rng=np.random.default_rng(0),
        key=jax.random.PRNGKey(1), fixed_c=c)
    rep = Session(
        strategy=CodedFL(key=jax.random.PRNGKey(1), fixed_c=c),
        fleet=fleet, lr=0.05, epochs=100).run(
            data, rng=np.random.default_rng(0))
    np.testing.assert_allclose(rep.nmse, errs, rtol=1e-3, atol=1e-6)
    assert rep.setup_time == pytest.approx(upload)
    assert rep.times[0] == pytest.approx(upload)  # upload delay included
    np.testing.assert_allclose(rep.epoch_durations, t_star)


def test_gradcoding_session_matches_legacy_trace(small):
    fleet, data = small
    rep = Session(strategy=GradientCodingFL(r=3), fleet=fleet, lr=0.05,
                  epochs=60).run(data, rng=np.random.default_rng(0))
    errs, durs, setup = _legacy_run_gradcoding(
        fleet, data, lr=0.05, epochs=60, rng=np.random.default_rng(0), r=3)
    np.testing.assert_allclose(rep.nmse, errs, rtol=1e-5, atol=1e-7)
    np.testing.assert_array_equal(rep.epoch_durations, durs)
    assert rep.setup_time == pytest.approx(setup)
    assert rep.setup_time > 0
    assert rep.times[0] == pytest.approx(rep.setup_time)
    # waiting for every group => gradient is exact => same NMSE trajectory
    # as synchronous uncoded FL (only the clock differs)
    unc = Session(strategy=UncodedFL(), fleet=fleet, lr=0.05,
                  epochs=60).run(data, rng=np.random.default_rng(0))
    np.testing.assert_allclose(rep.nmse, unc.nmse, rtol=2e-4, atol=1e-7)


# ---------------------------------------------------------------------------
# epoch schedules == the per-epoch sampling loops they replaced
# ---------------------------------------------------------------------------

def _frozen_uncoded_epochs(state, fleet, epochs, rng):
    """`UncodedFL.sample_epochs` as a per-epoch loop (frozen copy)."""
    durations = np.empty(epochs)
    for e in range(epochs):
        t_i = sample_total(fleet.edge, state.loads, rng)
        durations[e] = float(np.max(t_i))
    return EpochSchedule(durations=durations,
                         arrivals={"epoch": np.zeros(epochs, np.float32)})


def _frozen_coded_epochs(strategy, state, fleet, epochs, rng):
    """`CodedFL.sample_epochs` as a per-epoch loop (frozen copy)."""
    plan = state.plan
    n = fleet.edge.n
    t_star = plan.t_star
    upload_time = cfl.sample_parity_upload_time(state, fleet, rng)
    received = np.empty((epochs, n), dtype=np.float32)
    parity_ok = np.empty(epochs, dtype=np.float32)
    for e in range(epochs):
        t_i = sample_total(fleet.edge, plan.loads, rng)
        received[e] = (t_i <= t_star) & (plan.loads > 0)
        if strategy.server_always_returns or state.c == 0:
            parity_ok[e] = 1.0
        else:
            t_srv = sample_total(fleet.server, np.array([state.c]), rng)[0]
            parity_ok[e] = float(t_srv <= t_star)
    return EpochSchedule(
        durations=np.full(epochs, t_star),
        arrivals={"received": received, "parity_ok": parity_ok},
        setup_time=upload_time,
        t0=upload_time if strategy.include_upload_delay else 0.0)


def assert_same_schedule(got, want):
    np.testing.assert_array_equal(got.durations, want.durations)
    assert got.durations.dtype == want.durations.dtype
    assert got.arrivals.keys() == want.arrivals.keys()
    for k, v in want.arrivals.items():
        np.testing.assert_array_equal(got.arrivals[k], v)
        assert got.arrivals[k].dtype == v.dtype, k
    assert (got.setup_time, got.t0) == (want.setup_time, want.t0)


@pytest.mark.parametrize("fleet_kind", ["paper", "wireless"])
@pytest.mark.parametrize("variant", [
    "uncoded", "cfl", "cfl_server_always_returns", "cfl_c0",
    "cfl_no_upload_delay"])
def test_epoch_schedule_matches_frozen_loop(small, variant, fleet_kind):
    """Each strategy's `sample_epochs` and `sweep_inputs` give the frozen
    per-epoch loop's schedule bit for bit and leave the generator where
    the loop leaves it."""
    _, data = small
    if fleet_kind == "paper":
        fleet = paper_fleet(0.2, 0.2, seed=2, n=data.n, d=data.d)
    else:
        fleet = wireless_fleet(0.2, 0.2, nu_erasure=0.3, seed=2, n=data.n,
                               d=data.d)
    if variant == "uncoded":
        strat = UncodedFL()
    else:
        strat = CodedFL(
            key=jax.random.PRNGKey(4),
            fixed_c=0 if variant == "cfl_c0" else int(0.3 * data.m),
            server_always_returns=variant == "cfl_server_always_returns",
            include_upload_delay=variant != "cfl_no_upload_delay")
    state = strat.plan(fleet, data)
    if variant == "cfl_c0":
        assert state.c == 0
    epochs = 150
    seed = 2**32 + 5
    rng_old = np.random.default_rng(seed)
    if variant == "uncoded":
        want = _frozen_uncoded_epochs(state, fleet, epochs, rng_old)
    else:
        want = _frozen_coded_epochs(strat, state, fleet, epochs, rng_old)
    for draw in (strat.sample_epochs, strat.sweep_inputs):
        rng = np.random.default_rng(seed)
        assert_same_schedule(draw(state, fleet, epochs, rng), want)
        assert rng.bit_generator.state == rng_old.bit_generator.state


# ---------------------------------------------------------------------------
# gradient-coding exact recovery (property)
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(n_groups=st.integers(1, 4), r=st.integers(1, 3),
       d=st.integers(1, 10))
def test_gradcoding_recovers_exact_full_gradient(n_groups, r, d):
    """When every group has >= 1 non-straggler returner, the decoded
    gradient equals the exact full gradient (no LLN approximation)."""
    n = n_groups * r
    data = TrainData.linreg(jax.random.PRNGKey(n + 10 * r + 100 * d),
                            n=n, ell=6, d=d)
    fleet = paper_fleet(0.1, 0.1, seed=0, n=n, d=d)
    strat = GradientCodingFL(r=r)
    state = strat.plan(fleet, data)
    dev = strat.device_state(state, data)
    beta = jax.random.normal(jax.random.PRNGKey(0), (d,))
    g = strat.round_contributions(
        state, dev, beta,
        {"group_ok": jnp.ones(state.n_groups, dtype=jnp.float32)})
    full = oracle.full_gradient(data.xs, data.ys, beta)
    np.testing.assert_allclose(np.asarray(g), full, rtol=1e-4, atol=1e-4)


def test_gradcoding_partial_groups_drop_cleanly():
    """A straggling group contributes nothing; the rest stay exact."""
    data = TrainData.linreg(jax.random.PRNGKey(0), n=6, ell=5, d=4)
    fleet = paper_fleet(0.1, 0.1, seed=0, n=6, d=4)
    strat = GradientCodingFL(r=2)
    state = strat.plan(fleet, data)
    dev = strat.device_state(state, data)
    beta = jnp.zeros(4)
    ok = jnp.asarray([1.0, 0.0, 1.0], dtype=jnp.float32)
    g = strat.round_contributions(state, dev, beta, {"group_ok": ok})
    mask = np.repeat(np.asarray(ok), 2)  # fractional repetition: r=2
    per_client = oracle.client_gradients(data.xs, data.ys, beta)
    expect = np.einsum("nd,n->d", per_client, mask)
    np.testing.assert_allclose(np.asarray(g), expect, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Session mechanics
# ---------------------------------------------------------------------------

def test_session_engine_cache_reused_across_runs(small):
    fleet, data = small
    session = Session(strategy=UncodedFL(), fleet=fleet, lr=0.05, epochs=20)
    session.run(data, rng=np.random.default_rng(0))
    assert len(session._engines) == 1
    session.run(data, rng=np.random.default_rng(1))
    assert len(session._engines) == 1  # same shapes -> no retrace


def test_session_default_seed_reproducible(small):
    fleet, data = small
    session = Session(strategy=UncodedFL(), fleet=fleet, lr=0.05, epochs=20,
                      seed=7)
    a = session.run(data)
    b = session.run(data)
    np.testing.assert_array_equal(a.nmse, b.nmse)
    np.testing.assert_array_equal(a.epoch_durations, b.epoch_durations)


def test_report_helpers(small):
    fleet, data = small
    rep_u = Session(strategy=UncodedFL(), fleet=fleet, lr=0.05,
                    epochs=150).run(data)
    rep_c = Session(strategy=CodedFL(key=jax.random.PRNGKey(1),
                                     fixed_c=int(0.3 * data.m),
                                     include_upload_delay=False),
                    fleet=fleet, lr=0.05, epochs=150).run(data)
    tgt = 1e-1
    assert convergence_time(rep_u, tgt) > 0
    assert np.isfinite(convergence_time(rep_c, tgt))
    assert coding_gain(rep_u, rep_c, tgt) > 1.0
    assert rep_c.epochs == 150
    assert 0 < rep_c.epochs_to(tgt) <= 151
    assert rep_u.uplink_bits_total > 0


def test_custom_strategy_plugs_in(small):
    """The protocol is open: a user-defined scheme runs unmodified."""
    fleet, data = small

    class HalfFleetFL:
        """Toy scheme: only even-indexed clients ever report."""
        label = "half"

        def plan(self, fleet, data):
            return {"n": data.n}

        def sample_epochs(self, state, fleet, epochs, rng):
            from repro.api import EpochSchedule
            mask = np.zeros((epochs, state["n"]), np.float32)
            mask[:, ::2] = 1.0
            return EpochSchedule(durations=np.ones(epochs),
                                 arrivals={"received": mask})

        def device_state(self, state, data):
            return {"xs": data.xs, "ys": data.ys}

        def round_contributions(self, state, dev, beta, arrivals):
            xs, ys = dev["xs"], dev["ys"]
            resid = jnp.einsum("nld,d->nl", xs, beta) - ys
            partials = jnp.einsum("nld,nl->nd", xs, resid)
            return jnp.einsum("nd,n->d", partials, arrivals["received"])

        def uplink_bits(self, state, fleet, epochs):
            return 0.0

        def engine_key(self, state):
            return ()

    rep = Session(strategy=HalfFleetFL(), fleet=fleet, lr=0.05,
                  epochs=80).run(data)
    assert rep.label == "half"
    assert rep.final_nmse() < 1.0  # half the gradient still descends
    np.testing.assert_allclose(rep.epoch_durations, 1.0)
