"""Tests for the batched sweep engine (`repro.api.run_sweep`).

The load-bearing guarantee: a sweep lane is the SAME computation as a solo
`Session.run` — same planning, same per-lane generator draw order, and a
per-lane training program that is bit-for-bit identical at any lane count
(the engine iterates lanes with `lax.map` inside a `shard_map` precisely so
no batched lowering can perturb last-ulp arithmetic).  Every comparison
here is exact (`assert_array_equal`), not approximate.
"""
import dataclasses
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis, or deterministic fallback

from repro.api import (Session, TrainData, make_strategy, plan_sweep,
                       run_sweep)
from repro.api.session import _ENGINE_CACHE, _static_strategy_key
from repro.sim.network import paper_fleet, wireless_fleet

EPOCHS = 25
LR = 0.05


@pytest.fixture(scope="module")
def small():
    fleet = paper_fleet(0.2, 0.2, seed=1, n=12, d=40)
    wfleet = wireless_fleet(0.2, 0.2, nu_erasure=0.3, seed=0, n=12, d=40)
    data = TrainData.linreg(jax.random.PRNGKey(0), n=12, ell=60, d=40)
    return fleet, wfleet, data


def _sessions_for(name: str, small, epochs: int = EPOCHS):
    """A small sweep per strategy, lanes differing in value-only knobs."""
    fleet, wfleet, data = small
    c = int(0.3 * data.m)
    if name == "uncoded":
        return [Session(strategy=make_strategy("uncoded"), fleet=fleet,
                        lr=lr, epochs=epochs) for lr in (0.05, 0.03)]
    if name == "cfl":
        return [Session(strategy=make_strategy("cfl", key_seed=seed,
                                               fixed_c=c),
                        fleet=fleet, lr=LR, epochs=epochs)
                for seed in (7, 8, 9)]
    if name == "gradcode":
        return [Session(strategy=make_strategy("gradcode", r=3),
                        fleet=fleet, lr=lr, epochs=epochs)
                for lr in (0.05, 0.04)]
    if name == "stochastic":
        return [Session(strategy=make_strategy(
            "stochastic", key_seed=7, fixed_c=c, noise_multiplier=sigma,
            sample_frac=0.8, rounds=epochs),
            fleet=wfleet, lr=LR, epochs=epochs) for sigma in (0.0, 0.5, 1.0)]
    if name == "lowlatency":
        return [Session(strategy=make_strategy(
            "lowlatency", key_seed=seed, fixed_c=c, chunks=4),
            fleet=wfleet, lr=LR, epochs=epochs) for seed in (7, 11)]
    raise ValueError(name)


def _assert_lane_equals_solo(sweep_reports, sessions, data):
    """Bit-for-bit: traces, clocks, and extras match fresh solo runs."""
    for sess, rep in zip(sessions, sweep_reports):
        solo = sess.run(data, rng=np.random.default_rng(sess.seed))
        np.testing.assert_array_equal(rep.nmse, solo.nmse)
        np.testing.assert_array_equal(rep.times, solo.times)
        np.testing.assert_array_equal(rep.epoch_durations,
                                      solo.epoch_durations)
        assert rep.label == solo.label
        assert rep.setup_time == solo.setup_time
        assert rep.uplink_bits_total == solo.uplink_bits_total
        assert set(rep.extras) == set(solo.extras)
        for k, v in rep.extras.items():
            np.testing.assert_array_equal(np.asarray(v),
                                          np.asarray(solo.extras[k]))


# ---------------------------------------------------------------------------
# per-lane bit-parity with solo runs, all five registered strategies
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "name", ["uncoded", "cfl", "gradcode", "stochastic", "lowlatency"])
def test_sweep_lanes_bit_equal_solo(small, name):
    """The property, for every registered strategy: each sweep lane's NMSE
    trace, clock, and extras are bit-equal to a solo `Session.run` with
    the same per-lane generator."""
    _, _, data = small
    sessions = _sessions_for(name, small)
    reports = run_sweep(sessions, data)
    _assert_lane_equals_solo(reports, sessions, data)


def test_stochastic_sweep_preserves_privacy_extras(small):
    """Per-lane TraceReport.extras survive batching — including the DP
    accounting fields (`epsilon_spent`, `epsilon_schedule`)."""
    _, _, data = small
    sessions = _sessions_for("stochastic", small)
    reports = run_sweep(sessions, data)
    eps = [rep.extras["epsilon_spent"] for rep in reports]
    assert eps[0] == np.inf  # sigma = 0 lane: unbounded budget
    assert np.isfinite(eps[1]) and np.isfinite(eps[2])
    assert eps[1] > eps[2]  # more noise, less epsilon spent
    for rep in reports:
        assert rep.extras["epsilon_schedule"].shape == (EPOCHS,)
        assert rep.privacy_budget() is not None


# ---------------------------------------------------------------------------
# mixed-bucket sweeps: heterogeneous strategies and shapes in one call
# ---------------------------------------------------------------------------

def test_mixed_bucket_sweep(small):
    """One run_sweep over five strategy classes AND two parity-budget
    shapes: the bucketing path must split lanes by static structure +
    shapes and still reproduce every solo trace bit-for-bit."""
    fleet, wfleet, data = small
    c1, c2 = int(0.2 * data.m), int(0.4 * data.m)
    sessions = [
        Session(strategy=make_strategy("uncoded"), fleet=fleet, lr=LR,
                epochs=EPOCHS),
        Session(strategy=make_strategy("cfl", key_seed=7, fixed_c=c1),
                fleet=fleet, lr=LR, epochs=EPOCHS),
        Session(strategy=make_strategy("cfl", key_seed=7, fixed_c=c2),
                fleet=fleet, lr=LR, epochs=EPOCHS),
        Session(strategy=make_strategy("gradcode", r=3), fleet=fleet,
                lr=LR, epochs=EPOCHS),
        Session(strategy=make_strategy("stochastic", key_seed=7, fixed_c=c1,
                                       noise_multiplier=0.5),
                fleet=wfleet, lr=LR, epochs=EPOCHS),
        Session(strategy=make_strategy("lowlatency", key_seed=7, fixed_c=c1,
                                       chunks=4),
                fleet=wfleet, lr=LR, epochs=EPOCHS),
    ]
    reports = run_sweep(sessions, data)
    assert len(reports) == len(sessions)
    _assert_lane_equals_solo(reports, sessions, data)


def test_value_only_knobs_share_one_engine(small):
    """Lanes differing only in declared value-only knobs (lr, PRNG key,
    noise level) form ONE bucket: exactly one new engine entry appears."""
    _, _, data = small
    sessions = _sessions_for("stochastic", small)
    states = plan_sweep(sessions, data)
    before = len(_ENGINE_CACHE)
    run_sweep(sessions, data, states=states)
    new = len(_ENGINE_CACHE) - before
    assert new <= 1  # 0 when an earlier test already compiled this bucket


def test_run_sweep_validates_lengths(small):
    fleet, _, data = small
    sessions = [Session(strategy=make_strategy("uncoded"), fleet=fleet,
                        lr=LR, epochs=5)]
    with pytest.raises(ValueError, match="states"):
        run_sweep(sessions, data, states=[])
    with pytest.raises(ValueError, match="generators"):
        run_sweep(sessions, data, rngs=[])


# ---------------------------------------------------------------------------
# engine-cache keying: full static strategy structure, not just engine_key
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _ScaledUncoded:
    """Regression vehicle: a static field (`scale`) steers the traced
    engine, but `engine_key` FORGETS it — the historical failure mode for
    sessions cloned via `dataclasses.replace`."""

    scale: float = 1.0
    label: str = "scaled"

    def plan(self, fleet, data):
        return {"n": data.n}

    def sample_epochs(self, state, fleet, epochs, rng):
        from repro.api import EpochSchedule
        return EpochSchedule(
            durations=np.ones(epochs),
            arrivals={"epoch": np.zeros(epochs, np.float32)})

    def device_state(self, state, data):
        return {"x": data.xs.reshape(data.m, data.d),
                "y": data.ys.reshape(data.m)}

    def round_contributions(self, state, dev, beta, arrivals):
        resid = dev["x"] @ beta - dev["y"]
        return self.scale * (resid @ dev["x"])  # static use of `scale`

    def uplink_bits(self, state, fleet, epochs):
        return 0.0

    def engine_key(self, state):
        return ()  # deliberately incomplete


def test_replaced_static_field_never_shares_engine(small):
    """Two sessions produced by `dataclasses.replace` with different
    static strategy fields must compile DIFFERENT engines, even when the
    strategy's own `engine_key` under-reports."""
    fleet, _, data = small
    s1 = Session(strategy=_ScaledUncoded(scale=1.0), fleet=fleet, lr=LR,
                 epochs=10)
    rep1 = s1.run(data)
    s2 = dataclasses.replace(
        s1, strategy=dataclasses.replace(s1.strategy, scale=0.25))
    rep2 = s2.run(data)
    # a shared engine would have baked scale=1.0 into s2's trace
    assert not np.array_equal(rep1.nmse, rep2.nmse)
    assert set(s1._engines) != set(s2._engines)
    # the quarter-scale engine really computes a quarter-scale first step
    g_full = np.asarray(_ScaledUncoded(1.0).round_contributions(
        None, s1.strategy.device_state(None, data),
        jnp.zeros(data.d), {}))
    g_quarter = np.asarray(s2.strategy.round_contributions(
        None, s2.strategy.device_state(None, data), jnp.zeros(data.d), {}))
    np.testing.assert_allclose(0.25 * g_full, g_quarter, rtol=1e-6)


def test_static_key_excludes_label_and_value_fields(small):
    """`label` and declared `engine_value_fields` never fragment buckets;
    trace-steering fields always do."""
    a = make_strategy("stochastic", key_seed=7, noise_multiplier=0.2,
                      label="lane_a")
    b = make_strategy("stochastic", key_seed=9, noise_multiplier=0.9,
                      label="lane_b")
    assert _static_strategy_key(a) == _static_strategy_key(b)
    c = dataclasses.replace(a, sample_frac=0.5)  # traced 1/(c*rho) changes
    assert _static_strategy_key(a) != _static_strategy_key(c)


# ---------------------------------------------------------------------------
# lane mesh helpers (repro.launch)
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(n_lanes=st.integers(1, 40))
def test_lane_mesh_size_divides(n_lanes):
    from repro.launch.mesh import lane_mesh_size
    k = lane_mesh_size(n_lanes)
    assert 1 <= k <= max(1, len(jax.devices()))
    assert n_lanes % k == 0


def _place(counts, weights, n_devices):
    from repro.launch.mesh import place_lanes
    return place_lanes(list(zip(counts, weights)), n_devices)


def _per_device(chunks, n_devices):
    lanes = [0] * n_devices
    for _, positions, ids in chunks:
        for d in ids:
            lanes[d] += len(positions) // len(ids)
    return lanes


_BUCKETS = dict(counts=st.lists(st.integers(1, 20), min_size=1, max_size=6),
                n_devices=st.integers(1, 8))


@settings(max_examples=40, deadline=None)
@given(weight=st.sampled_from([1, 512, 7200]), **_BUCKETS)
def test_place_lanes_spreads_equal_lanes(counts, n_devices, weight):
    """Every lane placed once, each chunk a multiple of its devices, a
    bucket the devices divide one chunk on all of them, and with equal
    weights no device more than one lane ahead of another."""
    chunks = _place(counts, [weight] * len(counts), n_devices)
    for b, n in enumerate(counts):
        mine = [c for c in chunks if c[0] == b]
        placed = [p for _, positions, _ in mine for p in positions]
        assert sorted(placed) == list(range(n))
        if n % n_devices == 0:
            assert mine == [(b, tuple(range(n)), tuple(range(n_devices)))]
    for _, positions, ids in chunks:
        assert len(positions) % len(ids) == 0
        assert list(ids) == sorted(set(ids))
        assert all(0 <= d < n_devices for d in ids)
    lanes = _per_device(chunks, n_devices)
    assert max(lanes) - min(lanes) <= 1
    assert sum(lanes) == sum(counts)


@settings(max_examples=40, deadline=None)
@given(weights=st.lists(st.integers(1, 8000), min_size=6, max_size=6),
       **_BUCKETS)
def test_place_lanes_is_deterministic(counts, n_devices, weights):
    weights = weights[:len(counts)]
    chunks = _place(counts, weights, n_devices)
    assert chunks == _place(counts, weights, n_devices)
    assert sorted(p for c in chunks for p in c[1]) == sorted(
        p for n in counts for p in range(n))
    one = _place(counts, weights, 1)
    assert sorted(one) == [(b, tuple(range(n)), (0,))
                           for b, n in enumerate(counts)]


def test_place_lanes_sec4_fleet():
    """The §IV fleet's buckets (8 full-X lanes, 7 packed to 5,632 rows,
    1 packed to 5,120) on four devices: four lanes a device, the 7-lane
    bucket split 4 + 3 and the single lane on the fourth device."""
    chunks = _place([8, 7, 1], [7200, 5632, 5120], 4)
    assert chunks == [(0, tuple(range(8)), (0, 1, 2, 3)),
                      (1, (0, 1, 2, 3), (0, 1, 2, 3)),
                      (1, (4, 5, 6), (0, 1, 2)),
                      (2, (0,), (3,))]
    assert _per_device(chunks, 4) == [4, 4, 4, 4]


def test_place_lanes_rejects_empty_input():
    from repro.launch.mesh import place_lanes
    with pytest.raises(ValueError, match="n_devices"):
        place_lanes([(3, 1.0)], 0)
    with pytest.raises(ValueError, match="lane"):
        place_lanes([(0, 1.0)], 2)
    assert place_lanes([], 4) == []


def _split_sweep_check():
    """Run in a process with four host devices: a sweep whose buckets
    the devices do not divide (6 full-X lanes, 5 packed, 1 at c = 0)
    runs each chunk where `place_lanes` put it, counts two split
    buckets, and every lane stays bit-equal to its solo run."""
    from repro import obs

    assert len(jax.devices()) == 4
    fleet = paper_fleet(0.2, 0.2, seed=1, n=12, d=40)
    data = TrainData.linreg(jax.random.PRNGKey(0), n=12, ell=60, d=40)
    cs = [48, 72, 96, 120, 144, 168, 240, 264, 288, 312, 336, 0]
    sessions = [Session(strategy=make_strategy("cfl", key_seed=7 + j,
                                               fixed_c=c),
                        fleet=fleet, lr=LR, epochs=10, seed=j)
                for j, c in enumerate(cs)]
    states = plan_sweep(sessions, data)
    before = obs.counters()["lane_buckets_split"]
    reports = run_sweep(sessions, data, states=states)
    assert obs.counters()["lane_buckets_split"] - before == 2
    planned = [(0, 1, 2, 3)] * 4 + [(0, 1)] * 2 + [(0, 1, 2, 3)] * 4 \
        + [(2,), (3,)]
    for sess, ids in zip(sessions, planned):
        (key, engine), = sess._engines.items()
        assert key[2] == ids
        for sh in jax.tree.leaves(engine.output_shardings):
            assert sh.device_set == {jax.devices()[d] for d in ids}
    for rep, sess, state in zip(reports, sessions, states):
        solo = sess.run(data, rng=np.random.default_rng(sess.seed),
                        state=state)
        np.testing.assert_array_equal(rep.nmse, solo.nmse)
        np.testing.assert_array_equal(rep.beta, solo.beta)
        np.testing.assert_array_equal(rep.times, solo.times)
    assert obs.counters()["lane_buckets_split"] - before == 2


def test_split_buckets_on_four_devices_bit_equal_solo():
    """`_split_sweep_check` in a child process, the only place the
    tier-1 suite sees four devices and so a bucket split over them."""
    here = Path(__file__).resolve().parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(here.parent / "src"), str(here)]),
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") + " --xla_force_"
                          "host_platform_device_count=4").strip())
    proc = subprocess.run(
        [sys.executable, "-c",
         "import test_run_sweep as t; t._split_sweep_check()"],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-4000:]


def test_lane_specs_layout():
    from jax.sharding import PartitionSpec as P
    from repro.launch.sharding import lane_specs
    tree = {"a": np.zeros((4, 3, 2)), "b": np.zeros(4)}
    specs = lane_specs(tree)
    assert specs["a"] == P("lanes", None, None)
    assert specs["b"] == P("lanes")
