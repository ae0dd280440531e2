"""The multi-epoch delay sampler (`core.delay_model.sample_epoch_totals`)
against the per-epoch `sample_total` / `sample_total_mec` loop it stands
for: the same arrays bit for bit, and the generator left in the same
state, so later draws by any caller run on unchanged."""
import numpy as np
import pytest

from repro.core.delay_model import (DeviceDelayParams, sample_epoch_totals,
                                    sample_total, sample_total_mec)
from repro.sim.network import paper_fleet, wireless_fleet

N = 9
SERVER = DeviceDelayParams(a=[2e-4], mu=[1e4], tau=[0.0], p=[0.0])


def _hand(p):
    """A fleet with erasure probabilities `p`, cycled over N devices."""
    rng = np.random.default_rng(4)
    a = rng.uniform(1e-3, 5e-2, N)
    return DeviceDelayParams(a=a, mu=(2.0 / a) * rng.uniform(0.5, 2.0, N),
                             tau=rng.uniform(1e-3, 5e-2, N),
                             p=np.resize(np.asarray(p, np.float64), N))


def _fleet(name):
    """(edge, server) for each fleet shape the sampler must hold to."""
    if name == "paper":
        f = paper_fleet(0.2, 0.2, seed=3, n=N, d=30)
        return f.edge, f.server
    if name == "wireless":
        f = wireless_fleet(0.2, 0.2, nu_erasure=0.3, seed=3, n=N, d=30)
        return f.edge, f.server
    if name == "tau0":  # one device with no link: Geometric(1) among q
        e = _hand(0.1)
        tau = e.tau.copy()
        tau[2] = 0.0
        return DeviceDelayParams(e.a, e.mu, tau, e.p), SERVER
    if name == "mixed":
        return _hand([0.1, 0.7, 0.9]), SERVER
    # a uniform hand-built fleet: q = 1 - p reaches both of numpy's
    # geometric methods (search for q >= 1/3, inversion below)
    return _hand(float(name[1:])), SERVER


def _loop(groups, epochs, rng, mec):
    """The per-epoch loop the sampler replaces."""
    sample = sample_total_mec if mec else sample_total
    out = [np.empty((epochs, params.n)) for params, _ in groups]
    for e in range(epochs):
        for g, (params, ell) in enumerate(groups):
            out[g][e] = sample(params, ell, rng)
    return out


@pytest.mark.parametrize("mec", [False, True], ids=["base", "mec"])
@pytest.mark.parametrize("epochs", [1, 7, 600])
@pytest.mark.parametrize("with_server", [False, True],
                         ids=["edge", "edge+server"])
@pytest.mark.parametrize("fleet", ["paper", "wireless", "p0.1", "p0.7",
                                   "p0.9", "mixed", "tau0"])
def test_epoch_totals_match_per_epoch_loop(fleet, with_server, epochs, mec):
    edge, server = _fleet(fleet)
    loads = np.arange(N) * 17 % 50
    assert loads[0] == 0
    groups = [(edge, loads)]
    if with_server:
        groups.append((server, np.array([123])))
    seed = 2**31 + 11
    rng_loop = np.random.default_rng(seed)
    rng_new = np.random.default_rng(seed)
    want = _loop(groups, epochs, rng_loop, mec)
    got = sample_epoch_totals(groups, epochs, rng_new, mec=mec)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)
    assert rng_new.bit_generator.state == rng_loop.bit_generator.state
    # a caller's next draws continue unchanged
    np.testing.assert_array_equal(rng_new.random(5), rng_loop.random(5))


@pytest.mark.parametrize("q", [1.0, 0.9, 0.5, 0.3, 0.1])
def test_generator_identities_the_sampler_rests_on(q):
    """`standard_exponential` is `exponential(1.0)` draw for draw (into an
    `out` row too); a scalar success probability and its (n,) array draw
    the same geometrics; and two consecutive calls of one distribution
    draw what one call of their joined size draws."""
    n = 7
    ref, new = np.random.default_rng(5), np.random.default_rng(5)
    want_exp = [ref.exponential(1.0, size=n) for _ in range(3)]
    want_geo = [ref.geometric(np.full(n, q), size=n) for _ in range(2)]
    out = np.empty((3, n))
    new.standard_exponential(out=out[0])
    np.testing.assert_array_equal(out[0], want_exp[0])
    np.testing.assert_array_equal(new.standard_exponential((2, n)),
                                  np.stack(want_exp[1:]))
    np.testing.assert_array_equal(new.geometric(q, size=(2, n)),
                                  np.stack(want_geo))
    assert new.bit_generator.state == ref.bit_generator.state
