"""Tests for the non-linear layer: `CodedFedL` (arXiv:2007.03273), the
RFF feature map, and the MEC delay objective in the batched planner.

Three layers of guarantees, mirroring `tests/test_schemes.py`:

  * construction parity — `rff_map` matches its float64 NumPy oracle and
    approximates the Gaussian kernel; the MEC grid objective reproduces
    the scalar oracle in `plan/reference_schemes.py` (loads identical,
    t* within 1e-3 rel — both sides solved at eps_rel=1e-4, since at the
    default grid resolution interior loads can shift by one purely from
    t* rounding);
  * degenerate equivalence — `CodedFedL(d_feat=None)` IS `CodedFL`
    bit-for-bit from the same key (identity feature map, base delay
    model, same plan group);
  * composition — the strategy runs unmodified under `Session`,
    `run_sweep` (lanes bit-equal to solo), the serving engine (prefix
    parity), and `HierarchicalCFL` (single-tier exactness).

Plus the executable-docs gate's extraction unit tests (`scripts/
check_docs.py` is a CI stage; its block parser is load-bearing).
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis, or a deterministic fallback

from benchmarks.perf_trend import classify
from repro.api import Session, TrainData, make_strategy, run_sweep
from repro.core import cfl as cfl_core
from repro.core.delay_model import (mec_total_cdf, sample_total,
                                    sample_total_mec)
from repro.data import (classification_dataset, one_vs_rest_targets,
                        rff_map, rff_map_reference)
from repro.fleet import FleetTopology
from repro.plan import PlanRequest, solve_redundancy_batched
from repro.plan.reference_schemes import solve_codedfedl_reference
from repro.schemes import CodedFedL
from repro.serving import ConvergenceCriterion, FedServeEngine
from repro.sim.network import paper_fleet, wireless_fleet

from test_schemes import _random_fleet

N, ELL, D_RAW, D_FEAT = 12, 60, 6, 32
LR = 0.3
EPOCHS = 40


@pytest.fixture(scope="module")
def kernel_small():
    """Classification fixture: wireless fleet + RFF-space reference head."""
    fleet = wireless_fleet(0.2, 0.2, nu_erasure=0.3, seed=0, n=N, d=D_FEAT)
    xs, labels = classification_dataset(jax.random.PRNGKey(2), N, ELL, D_RAW,
                                        n_classes=2, centers=16, gamma=2.0)
    ys = one_vs_rest_targets(labels, 1)
    strat = make_strategy("codedfedl", key_seed=7, d_feat=D_FEAT,
                          rff_gamma=2.0 / D_RAW, fixed_c=int(0.3 * N * ELL))
    phi = np.asarray(strat.features(TrainData(
        xs=xs, ys=ys, beta_true=jnp.zeros(D_FEAT))), np.float64)
    beta_ref, *_ = np.linalg.lstsq(phi.reshape(-1, D_FEAT),
                                   np.asarray(ys, np.float64).ravel(),
                                   rcond=None)
    data = TrainData(xs=xs, ys=ys,
                     beta_true=jnp.asarray(beta_ref, jnp.float32))
    return fleet, data, strat


@pytest.fixture(scope="module")
def linreg_small():
    """Linear fixture where d_raw == d_feat, so CodedFL and kernel-mode
    CodedFedL train the same model width from the same TrainData."""
    fleet = wireless_fleet(0.2, 0.2, nu_erasure=0.3, seed=0, n=N, d=40)
    data = TrainData.linreg(jax.random.PRNGKey(0), n=N, ell=ELL, d=40)
    return fleet, data


# ---------------------------------------------------------------------------
# the RFF feature map
# ---------------------------------------------------------------------------

def test_rff_map_deterministic_and_shaped():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, D_RAW))
    key = jax.random.PRNGKey(1)
    z1 = rff_map(x, D_FEAT, key, gamma=0.7)
    z2 = rff_map(x, D_FEAT, key, gamma=0.7)
    assert z1.shape == (3, 5, D_FEAT)
    np.testing.assert_array_equal(np.asarray(z1), np.asarray(z2))
    z3 = rff_map(x, D_FEAT, jax.random.PRNGKey(2), gamma=0.7)
    assert np.abs(np.asarray(z1) - np.asarray(z3)).max() > 1e-3
    # unit diagonal: z(x).z(x) = (2/D) * sum(cos^2 + sin^2) = 1 exactly
    np.testing.assert_allclose(
        np.sum(np.asarray(z1, np.float64) ** 2, axis=-1), 1.0, rtol=1e-5)


def test_rff_map_matches_float64_oracle():
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (40, D_RAW)))
    key = jax.random.PRNGKey(4)
    got = np.asarray(rff_map(x, D_FEAT, key, gamma=1.3), np.float64)
    ref = rff_map_reference(x, D_FEAT, key, gamma=1.3)
    np.testing.assert_allclose(got, ref, atol=5e-6)


def test_rff_inner_products_approximate_gaussian_kernel():
    d_feat = 4096
    gamma = 0.5
    u, v = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (2, 8, 4)))
    zu = rff_map_reference(u, d_feat, jax.random.PRNGKey(6), gamma=gamma)
    zv = rff_map_reference(v, d_feat, jax.random.PRNGKey(6), gamma=gamma)
    approx = np.sum(zu * zv, axis=-1)
    exact = np.exp(-gamma * np.sum((u - v) ** 2, axis=-1))
    # error ~ 1/sqrt(d_feat); 0.05 is ~3 sigma at 4096 features
    np.testing.assert_allclose(approx, exact, atol=0.05)


def test_rff_map_validates_feature_count():
    x = np.zeros((2, 3))
    with pytest.raises(ValueError, match="even"):
        rff_map(x, 7, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="even"):
        rff_map(x, 0, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="even"):
        CodedFedL(key=jax.random.PRNGKey(0), d_feat=9)


# ---------------------------------------------------------------------------
# the MEC delay model + planner objective
# ---------------------------------------------------------------------------

def test_mec_cdf_monotone_bounded_and_shifted():
    edge, _ = _random_fleet(np.random.default_rng(7), 6)
    ell = np.array([10.0, 25.0, 0.0, 15.0, 30.0, 8.0])
    ts = np.linspace(0.0, 20.0, 60)
    prev = np.zeros(6)
    for t in ts:
        cur = mec_total_cdf(edge, ell, t)
        assert np.all((cur >= 0.0) & (cur <= 1.0))
        assert np.all(cur >= prev - 1e-12)       # monotone in t
        prev = cur
    # nothing returns before the deterministic shift (compute floor
    # a*ell plus two uplink slots)
    shift = edge.a * ell + 2.0 * edge.tau
    t_lo = 0.5 * shift[np.nonzero(ell)].min()
    early = mec_total_cdf(edge, ell, t_lo)
    assert np.all(early[np.nonzero(ell)] == 0.0)
    # a zero-load device has nothing to compute or send: done at t >= 0
    assert early[2] == 1.0


def test_mec_sampler_matches_cdf():
    edge, _ = _random_fleet(np.random.default_rng(11), 4)
    ell = np.array([12.0, 30.0, 20.0, 6.0])
    rng = np.random.default_rng(0)
    draws = np.stack([sample_total_mec(edge, ell, rng)
                      for _ in range(4000)])          # (trials, n)
    for t in (np.quantile(draws, 0.3), np.quantile(draws, 0.7)):
        emp = (draws <= t).mean(axis=0)
        np.testing.assert_allclose(emp, mec_total_cdf(edge, ell, t),
                                   atol=0.03)


@settings(max_examples=8, deadline=None)
@given(n=st.integers(2, 8), ell=st.integers(8, 60),
       mode=st.sampled_from(["free", "fixed"]), seed=st.integers(0, 10**6))
def test_mec_objective_matches_reference(n, ell, mode, seed):
    """MEC grid solve == scalar oracle (loads exact, t* 1e-3) — both at
    eps_rel=1e-4 so interior loads can't shift from t* rounding."""
    rng = np.random.default_rng(seed)
    edge, server = _random_fleet(rng, n)
    sizes = rng.integers(ell // 2 + 1, ell + 1, size=n)
    m = int(sizes.sum())
    kw = {"fixed_c": int(rng.integers(m // 10 + 1, m + 1))} \
        if mode == "fixed" else \
        {"c_up": int(rng.integers(m // 10 + 1, m + 1))}
    ref = solve_codedfedl_reference(edge, server, sizes, eps_rel=1e-4, **kw)
    new = solve_redundancy_batched(
        [PlanRequest(edge, server, sizes, mec_comm=True, **kw)],
        eps_rel=1e-4)[0]
    np.testing.assert_allclose(new.t_star, ref.t_star, rtol=1e-3)
    np.testing.assert_array_equal(new.loads, ref.loads)
    assert new.c == ref.c


def test_mixed_mec_batch_matches_solo():
    """Base and MEC requests in ONE batched call solve exactly as they do
    alone (the static flag groups them; neither perturbs the other)."""
    rng = np.random.default_rng(13)
    edge, server = _random_fleet(rng, 6)
    sizes = np.full(6, 40)
    reqs = [
        PlanRequest(edge, server, sizes, c_up=100),
        PlanRequest(edge, server, sizes, c_up=100, mec_comm=True),
        PlanRequest(edge, server, sizes, fixed_c=60, mec_comm=True),
    ]
    batch = solve_redundancy_batched(reqs)
    for req, got in zip(reqs, batch):
        solo = solve_redundancy_batched([req])[0]
        assert got.t_star == solo.t_star
        np.testing.assert_array_equal(got.loads, solo.loads)
        assert got.c == solo.c
    # the MEC law is a different CDF: same fleet, different return
    # probabilities (t* may still land on the same grid point)
    assert np.abs(batch[1].p_return - batch[0].p_return).max() > 0


def test_mec_comm_rejects_edge_chunks():
    rng = np.random.default_rng(0)
    edge, server = _random_fleet(rng, 3)
    with pytest.raises(ValueError, match="mec_comm"):
        PlanRequest(edge, server, np.full(3, 10), mec_comm=True,
                    edge_chunks=4)


# ---------------------------------------------------------------------------
# degenerate equivalence with CodedFL
# ---------------------------------------------------------------------------

def test_codedfedl_identity_map_degenerates_to_cfl(linreg_small):
    """d_feat=None: identity features, base delay model — same plan, same
    parity, bit-identical trace from the same key."""
    fleet, data = linreg_small
    c = int(0.3 * data.m)
    key = jax.random.PRNGKey(5)
    cfl = Session(strategy=make_strategy("cfl", key=key, fixed_c=c),
                  fleet=fleet, lr=0.05, epochs=80)
    cfedl = Session(strategy=CodedFedL(key=key, fixed_c=c),
                    fleet=fleet, lr=0.05, epochs=80)
    st_c, st_f = cfl.plan(data), cfedl.plan(data)
    assert st_c.plan.t_star == st_f.plan.t_star
    np.testing.assert_array_equal(st_c.plan.loads, st_f.plan.loads)
    np.testing.assert_array_equal(np.asarray(st_c.x_parity),
                                  np.asarray(st_f.x_parity))
    r_c = cfl.run(data, rng=np.random.default_rng(3), state=st_c)
    r_f = cfedl.run(data, rng=np.random.default_rng(3), state=st_f)
    np.testing.assert_array_equal(r_f.nmse, r_c.nmse)
    np.testing.assert_array_equal(r_f.times, r_c.times)
    np.testing.assert_array_equal(r_f.epoch_durations, r_c.epoch_durations)
    assert r_f.setup_time == r_c.setup_time


def _frozen_codedfedl_epochs(strategy, state, fleet, epochs, rng, mec):
    """`CodedFedL.sample_epochs` as a per-epoch loop (frozen copy)."""
    plan = state.plan
    n = fleet.edge.n
    t_star = plan.t_star
    sampler = sample_total_mec if mec else sample_total
    upload_time = cfl_core.sample_parity_upload_time(state, fleet, rng)
    received = np.empty((epochs, n), dtype=np.float32)
    parity_ok = np.empty(epochs, dtype=np.float32)
    for e in range(epochs):
        t_i = sampler(fleet.edge, plan.loads, rng)
        received[e] = (t_i <= t_star) & (plan.loads > 0)
        if strategy.server_always_returns or state.c == 0:
            parity_ok[e] = 1.0
        else:
            t_srv = sampler(fleet.server, np.array([state.c]), rng)[0]
            parity_ok[e] = float(t_srv <= t_star)
    return received, parity_ok, upload_time, \
        upload_time if strategy.include_upload_delay else 0.0


@pytest.mark.parametrize("server_always_returns", [False, True],
                         ids=["server", "no_server"])
@pytest.mark.parametrize("fleet_kind", ["paper", "wireless"])
@pytest.mark.parametrize("mec", [False, True], ids=["base", "mec"])
def test_codedfedl_epochs_match_frozen_loop(linreg_small, mec, fleet_kind,
                                            server_always_returns):
    """CodedFedL's schedule on either delay model, bit for bit the frozen
    per-epoch loop's, with the generator left where the loop leaves it."""
    _, data = linreg_small
    if fleet_kind == "paper":
        fleet = paper_fleet(0.2, 0.2, seed=6, n=N, d=data.d)
    else:
        fleet = wireless_fleet(0.2, 0.2, nu_erasure=0.3, seed=6, n=N,
                               d=data.d)
    strat = CodedFedL(key=jax.random.PRNGKey(8), mec_comm=mec,
                      fixed_c=int(0.25 * data.m),
                      server_always_returns=server_always_returns)
    state = strat.plan(fleet, data)
    epochs, seed = 90, 2**31 + 7
    rng_old = np.random.default_rng(seed)
    received, parity_ok, setup, t0 = _frozen_codedfedl_epochs(
        strat, state, fleet, epochs, rng_old, mec)
    for draw in (strat.sample_epochs, strat.sweep_inputs):
        rng = np.random.default_rng(seed)
        sched = draw(state, fleet, epochs, rng)
        np.testing.assert_array_equal(sched.arrivals["received"], received)
        np.testing.assert_array_equal(sched.arrivals["parity_ok"], parity_ok)
        assert sched.arrivals["received"].dtype == np.float32
        assert sched.arrivals["parity_ok"].dtype == np.float32
        np.testing.assert_array_equal(sched.durations,
                                      np.full(epochs, state.plan.t_star))
        assert (sched.setup_time, sched.t0) == (setup, t0)
        assert rng.bit_generator.state == rng_old.bit_generator.state


# ---------------------------------------------------------------------------
# registry + end-to-end kernel training
# ---------------------------------------------------------------------------

def test_registry_constructs_codedfedl():
    s = make_strategy("codedfedl", key_seed=1, d_feat=16)
    assert isinstance(s, CodedFedL) and s.d_feat == 16
    alias = make_strategy("cfedl", key_seed=1, d_feat=16)
    assert isinstance(alias, CodedFedL)
    with pytest.raises(ValueError, match="key"):
        make_strategy("codedfedl", d_feat=16)


def test_kernel_run_trains_and_reports(kernel_small):
    fleet, data, strat = kernel_small
    rep = Session(strategy=strat, fleet=fleet, lr=LR, epochs=EPOCHS).run(
        data, rng=np.random.default_rng(0))
    assert np.all(np.isfinite(rep.nmse))
    assert rep.final_nmse() < rep.nmse[0]
    assert rep.extras["d_feat"] == D_FEAT
    assert rep.extras["mec_comm"] == 1.0      # feature map => MEC model
    assert rep.extras["t_star"] > 0
    # the harvested head classifies better than chance on its own
    # training tiles (sanity, not the benchmark's held-out gate)
    phi = np.asarray(strat.features(data), np.float64).reshape(-1, D_FEAT)
    acc = np.mean((phi @ np.asarray(rep.beta, np.float64) > 0)
                  == (np.asarray(data.ys).ravel() > 0))
    assert acc > 0.6


# ---------------------------------------------------------------------------
# composition: sweep, serving, hierarchy
# ---------------------------------------------------------------------------

def test_codedfedl_sweeps_bit_equal_to_solo(linreg_small):
    """Mixed cfl/cfedl sweep: every lane bit-equal to its solo run (the
    kernel lane buckets separately — its operand is the feature stack)."""
    fleet, data = linreg_small
    c = int(0.25 * data.m)
    sessions = [
        Session(strategy=make_strategy("cfl", key_seed=5, fixed_c=c),
                fleet=fleet, lr=0.05, epochs=25, seed=1),
        Session(strategy=make_strategy("cfedl", key_seed=5, fixed_c=c,
                                       d_feat=data.d, rff_gamma=0.05),
                fleet=fleet, lr=0.05, epochs=25, seed=2),
        Session(strategy=make_strategy("cfedl", key_seed=9, fixed_c=c,
                                       d_feat=data.d, rff_gamma=0.05),
                fleet=fleet, lr=0.05, epochs=25, seed=3),
    ]
    reports = run_sweep(sessions, data)
    for sess, rep in zip(sessions, reports):
        solo = sess.run(data, rng=np.random.default_rng(sess.seed))
        np.testing.assert_array_equal(rep.nmse, solo.nmse)
        np.testing.assert_array_equal(rep.times, solo.times)


def test_codedfedl_serves_prefix_of_solo(kernel_small):
    fleet, data, strat = kernel_small
    sess = Session(strategy=strat, fleet=fleet, lr=LR, epochs=EPOCHS,
                   seed=21)
    engine = FedServeEngine(data, lane_width=2, chunk=10,
                            criterion=ConvergenceCriterion(nmse_target=0.0))
    [rep] = engine.serve([sess])
    solo = sess.run(data, rng=np.random.default_rng(sess.seed))
    t = rep.extras["serve_exit_epoch"]
    np.testing.assert_array_equal(rep.nmse, solo.nmse[:t + 1])
    np.testing.assert_array_equal(rep.times, solo.times[:t + 1])
    # kernel lanes get the plateau exit tightened in (serve_convergence)
    assert strat.serve_convergence(
        None, ConvergenceCriterion(nmse_target=0.0)).rel_delta is not None


def test_hierarchical_single_tier_codedfedl(kernel_small):
    fleet, data, strat = kernel_small
    solo = Session(strategy=strat, fleet=fleet, lr=LR, epochs=20,
                   seed=3).run(data, rng=np.random.default_rng(3))
    hier = make_strategy("hierarchical", base=strat,
                         topology=FleetTopology.uniform(N, 1))
    rep = Session(strategy=hier, fleet=fleet, lr=LR, epochs=20,
                  seed=3).run(data, rng=np.random.default_rng(3))
    np.testing.assert_array_equal(rep.nmse, solo.nmse)
    np.testing.assert_array_equal(rep.times, solo.times)


# ---------------------------------------------------------------------------
# the executable-docs gate + perf-trend coverage
# ---------------------------------------------------------------------------

def _load_check_docs():
    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "scripts", "check_docs.py")
    spec = importlib.util.spec_from_file_location("check_docs", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_docs_extracts_and_skips_blocks():
    cd = _load_check_docs()
    text = ("intro\n"
            "```python\nprint('runnable')\n```\n"
            "prose\n"
            "```python no-run\nraise SystemExit(1)\n```\n"
            "```python\nsessions = [Session(strategy=..., lr=lr)]\n```\n"
            "```bash\necho not python\n```\n")
    blocks = cd.extract_blocks(text)
    assert len(blocks) == 3                      # bash fence ignored
    (l1, i1, c1), (l2, i2, c2), (l3, i3, c3) = blocks
    assert l1 == 2 and cd.should_skip(i1, c1) is None
    assert "no-run" in cd.should_skip(i2, c2)
    assert "placeholder" in cd.should_skip(i3, c3)


def test_check_docs_example_table_is_complete():
    """Every examples/*.py has a deliberate CI-budget entry (a missing
    entry runs arg-less with only a notice — keep the table exhaustive)."""
    cd = _load_check_docs()
    ex_dir = os.path.join(cd.REPO, "examples")
    present = {f for f in os.listdir(ex_dir) if f.endswith(".py")}
    assert present == set(cd.EXAMPLE_ARGS)


def test_perf_trend_classifies_nonlinear_gates():
    assert classify("gates.coded_accuracy") == "higher"
    assert classify("gates.uncoded_accuracy_equal_time") == "higher"
    assert classify("gates.coded_final_nmse") == "lower"
