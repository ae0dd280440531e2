"""Float64 NumPy oracle of every strategy's round, for the tests.

Each strategy's epoch is restated here from the protocol it implements,
on the host, epoch by epoch, independent of the fused dispatchers in
`repro.core.aggregation` and the operand layouts of `repro.core.cfl`:

  * systematic rows weighted by the load mask and gated by the epoch's
    arrivals (`received`, `chunks_done` or `group_ok`);
  * the parity term (1 / (c * rho)) X~^T diag(mask * ok) (X~ beta - y~),
    with the `parity_mask` and rho = `sample_frac` where the scheme has
    them;
  * CodedFedL's feature matrix in place of X;
  * a hierarchical wrapper's client gates, summed over its tiers.

`run` replays a planned state over the `EpochSchedule` its strategy drew
and returns what a `Session` run reports: the NMSE trace, the epoch
clock and the final beta.
"""
import numpy as np

from repro.api import GradientCodingFL, UncodedFL
from repro.fleet import HierarchicalCFL
from repro.schemes import CodedFedL, LowLatencyCFL, StochasticCodedFL


def f64(a) -> np.ndarray:
    return np.asarray(a, dtype=np.float64)


def full_gradient(xs, ys, beta) -> np.ndarray:
    """X^T (X beta - y) over every client's every row (Eq. 2)."""
    x = f64(xs).reshape(-1, np.shape(xs)[-1])
    return x.T @ (x @ f64(beta) - f64(ys).reshape(-1))


def client_gradients(xs, ys, beta, load_mask=None) -> np.ndarray:
    """(n, d): client i's gradient over the rows its load mask keeps
    (every row when `load_mask` is None)."""
    xs = f64(xs)
    resid = xs @ f64(beta) - f64(ys)
    if load_mask is not None:
        resid = resid * f64(load_mask)
    return np.einsum("nld,nl->nd", xs, resid)


def _systematic(strategy, state, data, arr) -> tuple:
    """(X, y, w): the flat systematic rows and this epoch's row weights."""
    client = np.repeat(np.arange(data.n), data.ell)
    x = data.xs
    if isinstance(strategy, UncodedFL):
        w = np.ones(data.m)
    elif isinstance(strategy, GradientCodingFL):
        groups = np.asarray(state.plan.groups)
        w = f64(arr["group_ok"])[groups[client]]
    else:
        load = f64(state.load_mask).reshape(-1)
        if isinstance(strategy, LowLatencyCFL):
            # a row counts iff its chunk was done by t*
            done = f64(arr["chunks_done"])[client]
            w = load * (state.row_chunk.reshape(-1) < done)
        else:
            w = load * f64(arr["received"])[client]
        if isinstance(strategy, CodedFedL):
            x = state.features
    return f64(x).reshape(data.m, -1), f64(data.ys).reshape(-1), w


def _parity(strategy, state, arr, beta) -> np.ndarray:
    if isinstance(strategy, (UncodedFL, GradientCodingFL)) or state.c == 0:
        return np.zeros_like(beta)
    xp, yp = f64(state.x_parity), f64(state.y_parity)
    w = np.full(state.c, float(arr["parity_ok"]))
    rho = 1.0
    if isinstance(strategy, StochasticCodedFL):
        w = w * f64(arr["parity_mask"])
        rho = strategy.sample_frac
    return xp.T @ (w * (xp @ beta - yp)) / (state.c * rho)


def round_gradient(strategy, state, data, beta, arr) -> np.ndarray:
    """One epoch's gradient estimate for `arr`, one epoch's arrivals."""
    gate = 1.0
    if isinstance(strategy, HierarchicalCFL):
        client = np.repeat(np.arange(data.n), data.ell)
        tiers = f64(state.topology.tier_masks(data.ell)).sum(axis=0)
        gate = tiers * f64(arr["tier_gate"])[client]
        strategy, state = strategy.base, state.base
    x, y, w = _systematic(strategy, state, data, arr)
    return x.T @ (gate * w * (x @ beta - y)) \
        + _parity(strategy, state, arr, beta)


def run(strategy, state, data, sched, lr: float) -> tuple:
    """(nmse (epochs+1,), times (epochs+1,), final beta) of GD (Eq. 3)
    over `sched`, from beta = 0."""
    beta_true = f64(data.beta_true)
    beta = np.zeros_like(beta_true)

    def nmse(b):
        return np.sum((b - beta_true) ** 2) / np.sum(beta_true ** 2)

    trace = [nmse(beta)]
    for e in range(len(sched.durations)):
        arr = {k: v[e] for k, v in sched.arrivals.items()}
        g = round_gradient(strategy, state, data, beta, arr)
        beta = beta - (lr / data.m) * g
        trace.append(nmse(beta))
    times = sched.t0 + np.concatenate([[0.0], np.cumsum(sched.durations)])
    return np.asarray(trace), times, beta
