"""Coded federated learning, written out plainly (arXiv:2002.09574).

    fleet = paper_fleet(n=24, d=500, nu_comp=0.2, nu_link=0.2, seed=s)
    t_root = deadline(fleet, sizes, c=2016, ar=FLOAT64)
    plan = plan_at(fleet, sizes, 2016, t_star, FLOAT64)
    xp, yp = encode(key, xs, ys, weights(plan, ell), plan.c, FLOAT64)
    sched = sample_coded(fleet, plan, d, epochs, rng)
    nmse, beta = train(FLOAT64, x, y, beta_true, lr, ...)

Every function follows the paper's equations and the semantics the
program documents (the order of generator draws included), in the
precision of the `Arith` it is given; none of it calls the program.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .arith import FLOAT64, Arith

# terms kept of the negative-binomial retransmission series (Eq. 5-6)
K_MAX = 64


@dataclasses.dataclass(frozen=True)
class Fleet:
    """Delay parameters of n clients plus the server (§II-A, §IV)."""

    a: np.ndarray        # (n,) s of compute per point
    mu: np.ndarray       # (n,) memory-access rate, points/s
    tau: np.ndarray      # (n,) s per packet
    p: np.ndarray        # (n,) erasure probability
    a_srv: float
    mu_srv: float
    link_rates: np.ndarray
    packet_bits: float

    @property
    def n(self) -> int:
        return int(self.a.shape[0])


def paper_fleet(n: int, d: int, nu_comp: float, nu_link: float, seed: int,
                base_mac_kmacs: float = 1536.0,
                base_link_kbps: float = 216.0, erasure_p: float = 0.1,
                server_speedup: float = 10.0) -> Fleet:
    """§IV: geometric MAC-rate and link ladders, randomly assigned."""
    rng = np.random.default_rng(seed)
    ladder = np.arange(n)
    mac = rng.permutation((1.0 - nu_comp) ** ladder * base_mac_kmacs * 1e3)
    link = rng.permutation((1.0 - nu_link) ** ladder * base_link_kbps * 1e3)
    a = d / mac
    packet_bits = d * 32 * 1.1
    a_srv = d / (server_speedup * mac.max())
    return Fleet(a=a, mu=2.0 / a, tau=packet_bits / link,
                 p=np.full(n, float(erasure_p)), a_srv=float(a_srv),
                 mu_srv=float(2.0 / a_srv), link_rates=link,
                 packet_bits=float(packet_bits))


def wireless_fleet(n: int, d: int, nu_comp: float, nu_link: float,
                   nu_erasure: float, seed: int,
                   base_erasure_p: float = 0.3,
                   min_erasure_p: float = 0.02) -> Fleet:
    """§IV ladders plus per-client erasure probabilities on their own
    geometric ladder, max((1 - nu_erasure)^i * base, floor), randomly
    assigned first from the same generator (arXiv:2011.06223)."""
    rng = np.random.default_rng(seed)
    ladder = (1.0 - nu_erasure) ** np.arange(n) * base_erasure_p
    p = rng.permutation(np.maximum(ladder, min_erasure_p))
    mac = rng.permutation((1.0 - nu_comp) ** np.arange(n) * 1536.0e3)
    link = rng.permutation((1.0 - nu_link) ** np.arange(n) * 216.0e3)
    a = d / mac
    packet_bits = d * 32 * 1.1
    a_srv = d / (10.0 * mac.max())
    return Fleet(a=a, mu=2.0 / a, tau=packet_bits / link, p=p,
                 a_srv=float(a_srv), mu_srv=float(2.0 / a_srv),
                 link_rates=link, packet_bits=float(packet_bits))


def rff(x: np.ndarray, key, d_feat: int, gamma: float,
        ar: Arith = FLOAT64) -> np.ndarray:
    """Random Fourier features of the Gaussian kernel exp(-gamma |u-v|^2):
    W = sqrt(2 gamma) N(0, 1)^(d, d_feat/2) drawn in float32 from `key`,
    z(x) = sqrt(2 / d_feat) [cos(x W), sin(x W)] (Rahimi and Recht)."""
    g = np.asarray(jax.random.normal(key, (x.shape[-1], d_feat // 2),
                                     jnp.float32))
    w = ar.cast(np.sqrt(2.0 * gamma)) * ar.cast(g)
    proj = ar.mm(x.reshape(-1, x.shape[-1]), w)
    z = np.concatenate([np.cos(proj), np.sin(proj)], axis=-1)
    z *= ar.cast(np.sqrt(2.0 / d_feat))
    return z.reshape(x.shape[:-1] + (d_feat,))


# -- the plan (Eqs. 14-17) ---------------------------------------------------

def _cdf(a, mu, tau, p, ell, t, dt):
    """Pr{T <= t} at loads `ell` (..., n): compute time ell*a + Exp(mu/ell)
    plus (N_d + N_u) * tau, N ~ Geometric(1 - p); tau = 0 is the server."""
    one = dt(1.0)
    ell = np.asarray(ell, dt)
    t = dt(t)
    gamma = mu / np.maximum(ell, one)

    if not np.any(tau > 0):
        s = t - ell * a
        c = np.where(s > 0, -np.expm1(-np.minimum(gamma * np.maximum(s, 0),
                                                  dt(700.0))), dt(0.0))
        return np.where(ell > 0, c, (t >= 0).astype(dt))
    ks = np.arange(2, 2 + K_MAX, dtype=dt)
    pmf = (ks - one) * np.power(p[:, None], ks - 2) * (one - p[:, None]) ** 2
    resid = t - ks * tau[:, None]                        # (n, K)
    s = resid - (ell * a)[..., None]
    g = gamma[..., None]
    ck = np.where(s > 0, -np.expm1(-np.minimum(g * np.maximum(s, 0),
                                               dt(700.0))), dt(0.0))
    ck = np.where((ell <= 0)[..., None], (resid >= 0).astype(dt), ck)
    return np.sum(pmf * ck, axis=-1)


def _cdf_mec(a, mu, tau, p, ell, t, dt):
    """Pr{T <= t} under the MEC delay model (arXiv:2007.03273): compute
    time ell*a + Exp(mu/ell), communication 2 tau + Exp((1-p)/(2 tau p)),
    the closed-form convolution of the two exponentials (their equal-rate
    limit where the rates meet); a deterministic link (p or tau 0) leaves
    the compute time alone."""
    one = dt(1.0)
    ell = np.asarray(ell, dt)
    t = dt(t)
    cap = dt(700.0)
    gc = mu / np.maximum(ell, one)
    gm = (one - p) / np.maximum(dt(2.0) * tau * p, dt(1e-30))
    u = t - ell * a - dt(2.0) * tau
    up = np.maximum(u, dt(0.0))
    e_c = np.exp(-np.minimum(gc * up, cap))
    e_m = np.exp(-np.minimum(gm * up, cap))
    close = np.abs(gm - gc) <= dt(1e-8) * np.maximum(gm, gc)
    f_neq = one - (gm * e_c - gc * e_m) / np.where(close, one, gm - gc)
    arg = np.minimum(dt(0.5) * (gm + gc) * up, cap)
    f_eq = -np.expm1(-arg) - arg * np.exp(-arg)
    cdf = np.where(u > 0, np.where(close, f_eq, f_neq), dt(0.0))
    det = (p <= 0) | (tau <= 0)
    cdf_det = np.where(u > 0, -np.expm1(-np.minimum(gc * up, cap)),
                       dt(0.0))
    cdf = np.where(det, cdf_det, cdf)
    return np.where(ell > 0, cdf, (u >= 0).astype(dt))


CDF = {"base": _cdf, "mec": _cdf_mec}


@dataclasses.dataclass(frozen=True)
class Plan:
    loads: np.ndarray     # (n,) systematic rows per client
    c: int                # parity rows
    t_star: float         # epoch deadline
    p_return: np.ndarray  # (n+1,) Pr{T_i <= t*}, server last
    expected: float       # E[R(t*)], the aggregate expected return


def _params(fleet: Fleet, dt):
    edge = [np.asarray(v, dt) for v in (fleet.a, fleet.mu, fleet.tau,
                                        fleet.p)]
    srv = [np.asarray([v], dt) for v in (fleet.a_srv, fleet.mu_srv, 0.0,
                                         0.0)]
    return edge, srv


def _best(params, caps, t, dt, cdf=_cdf):
    """Each device's load in 1..cap that maximises ell Pr{T <= t} (Eq. 15),
    0 where no load returns anything; and that expected return."""
    grid = np.arange(1, int(caps.max()) + 1, dtype=dt)
    vals = grid[:, None] * cdf(*params, np.broadcast_to(
        grid[:, None], (grid.size, caps.size)), t, dt)
    vals = np.where(grid[:, None] <= caps[None, :], vals, -np.inf)
    idx = np.argmax(vals, axis=0)
    top = vals[idx, np.arange(vals.shape[1])]
    loads = np.where(top > 0, grid[idx], 0).astype(np.int64)
    return np.where(top > 0, top, 0), loads


def plan_at(fleet: Fleet, sizes: np.ndarray, c: int, t: float,
            ar: Arith = FLOAT64, model: str = "base") -> Plan:
    """The loads, return probabilities and expected return at deadline t,
    with the server's parity budget fixed at c, under the delay `model`
    ("base": geometric retransmissions, "mec": exponential link)."""
    dt = ar.dtype
    cdf = CDF[model]
    sizes = np.asarray(sizes, np.int64)
    edge, srv = _params(fleet, dt)
    ve, le = _best(edge, sizes, t, dt, cdf)
    vs, ls = _best(srv, np.array([c]), t, dt, cdf)
    p_ret = np.concatenate([cdf(*edge, le.astype(dt), t, dt),
                            cdf(*srv, ls.astype(dt), t, dt)])
    return Plan(loads=le, c=int(c), t_star=float(t),
                p_return=p_ret.astype(np.float64),
                expected=float(np.sum(ve, dtype=dt) + vs[0]))


def deadline(fleet: Fleet, sizes: np.ndarray, c: int,
             ar: Arith = FLOAT64, model: str = "base") -> float:
    """Eq. 16 at a fixed parity budget c: the least t at which the expected
    return reaches m, found by bisection to the precision of `ar`."""
    dt = ar.dtype
    sizes = np.asarray(sizes, np.int64)
    m = float(sizes.sum())
    edge, srv = _params(fleet, dt)
    mean = sizes * (edge[0] + 1 / edge[1]) + 2 * edge[2] / (1 - edge[3])
    t_hi = 1.0 + float(max(mean.max(), c * (srv[0][0] + 1 / srv[1][0])))
    while plan_at(fleet, sizes, c, t_hi, ar, model).expected < m:
        t_hi *= 2.0
    t_lo = 0.0
    for _ in range(200):
        mid = float(dt(0.5) * (dt(t_lo) + dt(t_hi)))
        if not t_lo < mid < t_hi:
            break
        if plan_at(fleet, sizes, c, mid, ar, model).expected >= m:
            t_hi = mid
        else:
            t_lo = mid
    return t_hi


def weights(plan: Plan, ell: int) -> np.ndarray:
    """Eq. 17: sqrt(Pr{T_i > t*}) on each client's first loads_i rows
    (the ones it processes), 1 on the rows it never processes."""
    n = plan.loads.shape[0]
    w = np.ones((n, ell))
    s = np.sqrt(np.maximum(0.0, 1.0 - plan.p_return[:n]))
    rows = np.arange(ell)[None, :] < plan.loads[:, None]
    return np.where(rows, s[:, None], w)


# -- one-time parity encode (Eqs. 9-12) ---------------------------------------

@functools.partial(jax.jit, static_argnames="shape")
def _normal(key, shape):
    return jax.random.normal(key, shape, dtype=jnp.float32)


def generator(key, i: int, n: int, c: int, ell: int) -> np.ndarray:
    """G_i (c, ell): iid N(0, 1) in float32, from the i-th of n splits of
    the session's key."""
    return np.asarray(_normal(jax.random.split(key, n)[i], (c, ell)))


def encode(key, xs: np.ndarray, ys: np.ndarray, w: np.ndarray, c: int,
           ar: Arith = FLOAT64):
    """(X~, y~) = sum_i G_i W_i [X_i, y_i]: returns (c, d) and (c,)."""
    n, ell, d = xs.shape
    acc = np.zeros((c, d + 1), ar.dtype)
    for i in range(n):
        g = generator(key, i, n, c, ell)
        xa = np.concatenate([ar.cast(xs[i]), ar.cast(ys[i])[:, None]], 1)
        acc += ar.mm(g, ar.cast(w[i])[:, None] * xa)
    return acc[:, :d], acc[:, d]


# -- the delay sampler (the program's documented draw order) ------------------

def _draw(fleet_arrays, ell, rng):
    a, mu, tau, p = fleet_arrays
    ell = np.broadcast_to(np.asarray(ell, np.float64), a.shape)
    with np.errstate(divide="ignore", invalid="ignore"):
        scale = np.where(ell > 0, ell / mu, 0.0)
    t_c = ell * a + rng.exponential(1.0, size=a.shape) * scale
    comm = tau > 0
    q = np.where(comm, p, 0.0)
    n_d = rng.geometric(1.0 - q, size=a.shape)
    n_u = rng.geometric(1.0 - q, size=a.shape)
    return t_c + np.where(comm, (n_d + n_u) * tau, 0.0)


def _draw_mec(fleet_arrays, ell, rng):
    """MEC: the same compute draw, then one exponential excess over the
    2 tau floor (two draws per device per call, whatever the load)."""
    a, mu, tau, p = fleet_arrays
    ell = np.broadcast_to(np.asarray(ell, np.float64), a.shape)
    with np.errstate(divide="ignore", invalid="ignore"):
        scale = np.where(ell > 0, ell / mu, 0.0)
    t_c = ell * a + rng.exponential(1.0, size=a.shape) * scale
    comm = tau > 0
    gm = (1.0 - p) / np.maximum(2.0 * tau * p, 1e-30)
    excess = rng.exponential(1.0, size=a.shape) / gm
    return t_c + np.where(comm, 2.0 * tau, 0.0) \
        + np.where(comm & (p > 0), excess, 0.0)


DRAW = {"base": _draw, "mec": _draw_mec}


@dataclasses.dataclass
class Schedule:
    received: np.ndarray   # (E, n) 1 where client i's update counts
    parity_ok: np.ndarray  # (E,) 1 where the parity gradient counts
    times: np.ndarray      # (E+1,) clock at each model snapshot


def _edge(fleet):
    return (fleet.a, fleet.mu, fleet.tau, fleet.p)


def sample_coded(fleet: Fleet, plan: Plan, d: int, epochs: int,
                 rng: np.random.Generator, model: str = "base"
                 ) -> Schedule:
    """CFL epochs: the one-time parity upload first (every client ships
    c (d+1) 32-bit values plus 10% header over its link, retransmitting
    Geometric(1-p) times), then per epoch each client's T_i against t* and
    the server's compute time on c rows, each drawn under `model`."""
    draw = DRAW[model]
    n = fleet.n
    bits = plan.c * (d + 1) * 32 * 1.1
    packets = np.ceil(bits / fleet.packet_bits)
    retrans = rng.geometric(1.0 - fleet.p, size=n)
    upload = float(np.max(packets * retrans
                          * (fleet.packet_bits / fleet.link_rates)))
    srv = (np.array([fleet.a_srv]), np.array([fleet.mu_srv]),
           np.zeros(1), np.zeros(1))
    received = np.empty((epochs, n))
    parity_ok = np.empty(epochs)
    for e in range(epochs):
        t_i = draw(_edge(fleet), plan.loads, rng)
        received[e] = (t_i <= plan.t_star) & (plan.loads > 0)
        t_s = draw(srv, np.array([plan.c]), rng)[0]
        parity_ok[e] = float(t_s <= plan.t_star)
    times = upload + np.concatenate([[0.0], np.cumsum(
        np.full(epochs, plan.t_star))])
    return Schedule(received, parity_ok, times)


def sample_uncoded(fleet: Fleet, ell: int, epochs: int,
                   rng: np.random.Generator) -> Schedule:
    """Synchronous FL: every epoch waits for the slowest client (Eq. 2)."""
    loads = np.full(fleet.n, ell)
    dur = np.array([np.max(_draw(_edge(fleet), loads, rng))
                    for _ in range(epochs)])
    return Schedule(np.ones((epochs, fleet.n)), np.zeros(epochs),
                    np.concatenate([[0.0], np.cumsum(dur)]))


# -- the epoch engine (Eqs. 3, 18, 19) ----------------------------------------

def train(ar: Arith, x: np.ndarray, y: np.ndarray, beta_true: np.ndarray,
          lr: float, row_w: np.ndarray, row_client: np.ndarray,
          received: np.ndarray, parity: Optional[tuple] = None,
          parity_ok: Optional[np.ndarray] = None):
    """Gradient descent from beta = 0 for len(received) epochs.

    Epoch e's gradient is the sum over clients i with received[e, i] of
    X_i^T (X_i beta - y_i) over the rows where row_w is 1 (Eq. 2), plus
    parity_ok[e] * X~^T (X~ beta - y~) / c where a parity block (X~, y~)
    is given (Eq. 18); then beta <- beta - (lr / m) g (Eq. 3).  Each sum
    is taken through its normal equations, X^T X beta - X^T y, formed
    once.  Returns the NMSE at every snapshot (E+1,) and the final beta,
    in float64.
    """
    dt = ar.dtype
    y, bt = ar.cast(y), ar.cast(beta_true)
    m = y.shape[0]
    step = dt(lr) / dt(m)
    grams = []
    for i in range(received.shape[1]):
        rows = (row_client == i) & (np.asarray(row_w) > 0)
        xi = ar.prep(np.asarray(x)[rows])
        grams.append((ar.prep(ar.mm(xi.T, xi)), ar.mm(xi.T, y[rows])))
    if parity is not None:
        xp = ar.prep(parity[0])
        inv_c = dt(1.0) / dt(parity[0].shape[0])
        par = (ar.prep(ar.mm(xp.T, xp)), ar.mm(xp.T, ar.cast(parity[1])))
    beta = np.zeros(bt.shape[0], dt)
    norm = np.sum(bt * bt)
    trace = [np.sum((beta - bt) ** 2) / norm]
    for e in range(received.shape[0]):
        g = np.zeros_like(beta)
        bp = ar.prep(beta)
        for i in np.flatnonzero(received[e]):
            g = g + (ar.mm(grams[i][0], bp) - grams[i][1])
        if parity is not None and parity_ok[e]:
            g = g + (ar.mm(par[0], bp) - par[1]) * inv_c
        beta = beta - step * g
        trace.append(np.sum((beta - bt) ** 2) / norm)
    return np.asarray(trace, np.float64), beta.astype(np.float64)
