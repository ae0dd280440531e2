"""Tests for gradient computation and deadline-masked aggregation (Eqs. 18-19)."""
import _oracle as oracle
import jax
import jax.numpy as jnp
import numpy as np
from _hyp import given, settings, st  # hypothesis, or a deterministic fallback

from repro.api import CodedFL, TrainData
from repro.core import aggregation as agg
from repro.core import cfl


def _data(key, n=6, ell=40, d=16):
    k1, k2, k3 = jax.random.split(key, 3)
    xs = jax.random.normal(k1, (n, ell, d))
    beta_true = jax.random.normal(k2, (d,))
    ys = jnp.einsum("nld,d->nl", xs, beta_true) + 0.1 * jax.random.normal(k3, (n, ell))
    return xs, ys, beta_true


def _coded_state(xs, loads, x_par=None, y_par=None):
    """A CodedFL protocol state with the given loads and parity block
    (none: c = 0); the fields the round does not read stay empty."""
    n, ell, d = xs.shape
    mask = jnp.asarray(np.arange(ell)[None, :] < np.asarray(loads)[:, None],
                       dtype=xs.dtype)
    if x_par is None:
        x_par = jnp.zeros((0, d), dtype=xs.dtype)
        y_par = jnp.zeros((0,), dtype=xs.dtype)
    return cfl.CFLState(plan=None, weights=None, load_mask=mask,
                        x_parity=x_par, y_parity=y_par, edge=None,
                        server=None)


# the packed and the dense fallback layout of `fused_coded_device_state`
LAYOUTS = (float("inf"), 0.0)


def test_uncoded_gradient_matches_flat():
    xs, ys, _ = _data(jax.random.PRNGKey(0))
    beta = jnp.zeros(xs.shape[-1])
    x_flat = xs.reshape(-1, xs.shape[-1])
    g = agg.round_gradient(x_flat, ys.reshape(-1), beta, path=agg.FUSED)
    np.testing.assert_allclose(np.asarray(g),
                               oracle.full_gradient(xs, ys, beta),
                               rtol=1e-4, atol=1e-4)


def test_partial_gradients_respect_load_mask(monkeypatch):
    """Each client's slice of the fused systematic block, at the block's
    base row weight, is its gradient over its first loads[i] points."""
    xs, ys, bt = _data(jax.random.PRNGKey(1))
    n, ell, d = xs.shape
    loads = np.array([0, 10, 40, 25, 1, 39])
    data = TrainData(xs=xs, ys=ys, beta_true=bt)
    beta = jax.random.normal(jax.random.PRNGKey(2), (d,))
    for frac in LAYOUTS:
        monkeypatch.setattr(cfl, "PACK_DENSE_FRAC", frac)
        dev = cfl.fused_coded_device_state(_coded_state(xs, loads), data)
        x, y, w0, client = agg.fused_sys_block(dev)
        for i in range(n):
            got = agg.round_gradient(x, y, beta, w=w0 * (client == i),
                                     path=agg.FUSED)
            xi = np.asarray(xs[i, :loads[i]], dtype=np.float64)
            yi = np.asarray(ys[i, :loads[i]], dtype=np.float64)
            expect = xi.T @ (xi @ np.asarray(beta, dtype=np.float64) - yi)
            np.testing.assert_allclose(np.asarray(got), expect,
                                       rtol=1e-4, atol=1e-4)


def test_full_coverage_sum_equals_total(monkeypatch):
    """Full loads, every client received, no parity => the fused round
    is the uncoded full gradient."""
    xs, ys, bt = _data(jax.random.PRNGKey(3))
    n, ell, d = xs.shape
    data = TrainData(xs=xs, ys=ys, beta_true=bt)
    beta = jax.random.normal(jax.random.PRNGKey(4), (d,))
    state = _coded_state(xs, np.full(n, ell))
    for frac in LAYOUTS:
        monkeypatch.setattr(cfl, "PACK_DENSE_FRAC", frac)
        state._fused_dev = None
        dev = cfl.fused_coded_device_state(state, data)
        g = CodedFL(key=jax.random.PRNGKey(0)).round_contributions(
            state, dev, beta, {"received": jnp.ones(n)})
        np.testing.assert_allclose(np.asarray(g),
                                   oracle.full_gradient(xs, ys, beta),
                                   rtol=1e-4, atol=1e-4)


def test_parity_gradient_lln():
    """(1/c) X~^T (X~ b - y~) -> X^T W^2 (X b - y) as c grows (Eq. 18),
    through the Gram-folded parity term the strategies run."""
    key = jax.random.PRNGKey(5)
    xs, ys, _ = _data(key, n=4, ell=30, d=8)
    n, ell, d = xs.shape
    w = jax.random.uniform(jax.random.PRNGKey(6), (n, ell), minval=0.2, maxval=1.0)
    beta = jax.random.normal(jax.random.PRNGKey(7), (d,))

    from repro.core.encoding import encode_fleet
    errs = []
    x_flat = np.asarray(xs).reshape(-1, d)
    y_flat = np.asarray(ys).reshape(-1)
    w_flat = np.asarray(w).reshape(-1)
    resid = x_flat @ np.asarray(beta) - y_flat
    target = x_flat.T @ (w_flat ** 2 * resid)
    for c in [200, 2000, 20000]:
        xp, yp = encode_fleet(jax.random.PRNGKey(8), xs, ys, w, c)
        gram, gramy = agg.parity_gram(xp, yp)
        g = np.asarray(agg.gram_parity_gradient(gram, gramy, beta,
                                                jnp.asarray(float(c))))
        errs.append(np.linalg.norm(g - target) / np.linalg.norm(target))
    assert errs[0] > errs[1] > errs[2]
    assert errs[2] < 0.05


def test_epoch_gradient_unbiased_monte_carlo():
    """E over (G, arrival masks) of the CFL round ~= full gradient."""
    key = jax.random.PRNGKey(9)
    xs, ys, bt = _data(key, n=5, ell=20, d=6)
    n, ell, d = xs.shape
    data = TrainData(xs=xs, ys=ys, beta_true=bt)
    rngs = np.random.default_rng(0)
    beta = jax.random.normal(jax.random.PRNGKey(10), (d,))

    # synthetic plan: each device processes first half; P(return) = 0.6
    loads = np.full(n, ell // 2)
    p_ret = 0.6
    w = np.ones((n, ell), dtype=np.float32)
    w[:, :ell // 2] = np.sqrt(1 - p_ret)

    from repro.core.encoding import encode_fleet

    strat = CodedFL(key=jax.random.PRNGKey(0))
    full = oracle.full_gradient(xs, ys, beta)
    acc = np.zeros(d)
    trials = 300
    c = 600
    for t in range(trials):
        xp, yp = encode_fleet(jax.random.PRNGKey(100 + t), xs, ys,
                              jnp.asarray(w), c)
        state = _coded_state(xs, loads, xp, yp)
        dev = cfl.fused_coded_device_state(state, data)
        received = jnp.asarray(rngs.random(n) < p_ret, dtype=xs.dtype)
        g = strat.round_contributions(
            state, dev, beta,
            {"received": received, "parity_ok": jnp.asarray(1.0)})
        acc += np.asarray(g)
    acc /= trials
    rel = np.linalg.norm(acc - full) / np.linalg.norm(full)
    assert rel < 0.08, rel


def test_gd_update_direction():
    xs, ys, beta_true = _data(jax.random.PRNGKey(11))
    beta = jnp.zeros(xs.shape[-1])
    g = oracle.full_gradient(xs, ys, beta)
    m = xs.shape[0] * xs.shape[1]
    beta2 = agg.gd_update(beta, jnp.asarray(g, dtype=beta.dtype), 0.01, m)
    np.testing.assert_allclose(np.asarray(beta2), -(0.01 / m) * g,
                               rtol=1e-5, atol=1e-7)
    assert agg.nmse(beta2, beta_true) < agg.nmse(beta, beta_true)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(1, 5), ell=st.integers(2, 16), d=st.integers(1, 12))
def test_combine_linear_in_masks(n, ell, d):
    """The CFL round is linear in the arrival masks (property): the
    clients in r1 with the parity, plus the rest without it, is the
    round where everything arrived."""
    rng = np.random.default_rng(n + 10 * ell + 100 * d)
    f32 = np.float32
    xs = jnp.asarray(rng.standard_normal((n, ell, d)), f32)
    ys = jnp.asarray(rng.standard_normal((n, ell)), f32)
    loads = rng.integers(0, ell + 1, size=n)
    xp = jnp.asarray(rng.standard_normal((3, d)), f32)
    yp = jnp.asarray(rng.standard_normal(3), f32)
    beta = jnp.asarray(rng.standard_normal(d), f32)
    r1 = (rng.random(n) < 0.5).astype(f32)
    state = _coded_state(xs, loads, xp, yp)
    dev = cfl.fused_coded_device_state(
        state, TrainData(xs=xs, ys=ys, beta_true=beta))
    strat = CodedFL(key=jax.random.PRNGKey(0))
    # one compile serves the three rounds
    step = jax.jit(lambda arr: strat.round_contributions(state, dev, beta,
                                                         arr))

    def rnd(received, parity_ok):
        return np.asarray(step({"received": jnp.asarray(received),
                                "parity_ok": jnp.asarray(parity_ok, f32)}))

    full = rnd(np.ones(n, f32), 1.0)
    part = rnd(r1, 1.0)
    rest = rnd(1.0 - r1, 0.0)
    np.testing.assert_allclose(part + rest, full, rtol=1e-4, atol=1e-4)
