"""Rényi-DP accountant for the subsampled Gaussian mechanism.

Maps `StochasticCodedFL`'s two knobs — `noise_multiplier` (Gaussian noise
std relative to the coded data's RMS) and `sample_frac` (per-round
Bernoulli parity-row sampling rate) — to a composed (epsilon, delta)
privacy budget over training, making the ROADMAP's "bare noise knob"
quantitative.  The model is the standard DP-SGD-style accountant shape
(Mironov 2017; Mironov-Talwar-Zhang 2019): each training round is one
release of a Poisson-subsampled Gaussian mechanism with sampling
probability `q = sample_frac` and noise multiplier `sigma =
noise_multiplier`; rounds compose additively in the RDP domain and the
total converts to (epsilon, delta) at the end.

Order grid (`DEFAULT_ORDERS`), a deliberate hybrid:

  * **integer orders 2..64** — the exact subsampled-Gaussian RDP via the
    binomial expansion (log-domain, stable at q = 1):

        A_alpha = sum_k C(alpha,k) (1-q)^(alpha-k) q^k e^(k(k-1)/(2 sigma^2))
        rdp(alpha) = log(A_alpha) / (alpha - 1)

  * **large orders 80..4096** — bounded by the UNSUBSAMPLED Gaussian RDP
    `alpha / (2 sigma^2)`.  Valid because subsampling only lowers RDP
    (A is a Binomial(alpha, q) expectation of a convex increasing
    function of k, so A(q) <= A(1)), and near-tight in this repo's
    high-`sample_frac` regime (SCFL samples most parity rows every
    round, unlike DP-SGD's tiny minibatch rates).  The large orders
    extend the achievable epsilon floor down to ~5e-4 at delta = 1e-5
    without a (B, S, A, 4096)-wide binomial tensor.

Every candidate order yields a VALID (epsilon, delta) bound, so the min
over the grid is valid; capping the grid only makes the answer
conservative.  RDP -> (epsilon, delta) uses the improved conversion
(Balle et al. 2020, the one production accountants ship):

    epsilon = min_alpha [ rdp(alpha) + log1p(-1/alpha)
                          - (log(delta) + log(alpha)) / (alpha - 1) ]

All arithmetic runs in float64 under a scoped `enable_x64` (the same
pattern as `repro.plan.solver`); the float64 NumPy oracle in
`repro.privacy.reference` mirrors these expressions loop-by-loop and the
two must agree to <= 1e-6 relative (tests/test_privacy.py).

The inverse problem — `calibrate_noise(epsilon_target, ...)` — lives in
`repro.privacy.calibrate` as a vectorized, jitted grid-then-polish solve.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# Exact-subsampled integer orders (binomial sum over k = 0..alpha).
SMALL_ORDERS = np.arange(2, 65, dtype=np.float64)
# Gaussian-bounded large orders: push the epsilon floor down for
# tight-privacy calibrations while keeping the k axis at 65 entries.
LARGE_ORDERS = np.array([80.0, 96.0, 128.0, 192.0, 256.0, 384.0, 512.0,
                         768.0, 1024.0, 1536.0, 2048.0, 3072.0, 4096.0])
DEFAULT_ORDERS = np.concatenate([SMALL_ORDERS, LARGE_ORDERS])

_KS = np.arange(0, int(SMALL_ORDERS[-1]) + 1, dtype=np.float64)
# log C(alpha, k) for the small integer orders; -inf marks k > alpha so
# logsumexp drops those terms exactly.
_LOG_BINOM = np.full((SMALL_ORDERS.size, _KS.size), -np.inf)
for _i, _alpha in enumerate(SMALL_ORDERS):
    for _k in range(int(_alpha) + 1):
        _LOG_BINOM[_i, _k] = (math.lgamma(_alpha + 1.0)
                              - math.lgamma(_k + 1.0)
                              - math.lgamma(_alpha - _k + 1.0))


def _rdp_all_orders(sigma, q):
    """Per-round RDP at every `DEFAULT_ORDERS` order (traceable).

    sigma, q: broadcast-compatible float arrays -> (..., A).  sigma == 0
    produces non-finite garbage here; callers mask it to +inf (zero noise
    means no privacy).
    """
    sig2 = (sigma * sigma)[..., None, None]
    logq = jnp.log(q)[..., None, None]
    # 0 * log(0) -> 0 by convention: at q == 1 the k < alpha terms carry
    # log(1-q) = -inf and vanish, while the k == alpha term (coefficient
    # exactly 0) takes the where's 0 branch — reproducing the pure
    # Gaussian RDP alpha / (2 sigma^2) exactly.
    log1mq = jnp.where(q < 1.0, jnp.log1p(-q), -jnp.inf)[..., None, None]
    coef = SMALL_ORDERS[:, None] - _KS[None, :]
    terms = (_LOG_BINOM + _KS * logq
             + jnp.where(coef > 0.0, coef * log1mq, 0.0)
             + _KS * (_KS - 1.0) / (2.0 * sig2))
    log_a = jax.scipy.special.logsumexp(terms, axis=-1)       # (..., As)
    rdp_small = log_a / (SMALL_ORDERS - 1.0)
    rdp_large = LARGE_ORDERS / (2.0 * sig2[..., 0])           # (..., Al)
    return jnp.concatenate([rdp_small, rdp_large], axis=-1)


def _eps_from_total_rdp(rdp_total, delta):
    """Improved RDP -> (epsilon, delta) conversion, min over the grid.

    rdp_total: (..., A) composed RDP;  delta: (...,) broadcastable.
    """
    a = DEFAULT_ORDERS
    eps = (rdp_total + jnp.log1p(-1.0 / a)
           - (jnp.log(delta)[..., None] + jnp.log(a)) / (a - 1.0))
    return jnp.maximum(jnp.min(eps, axis=-1), 0.0)


@jax.jit
def _epsilon_spent_grid(sigma, q, rounds, delta):
    """epsilon for broadcast (sigma, q, rounds, delta) arrays."""
    rdp = _rdp_all_orders(sigma, q) * rounds[..., None]
    return jnp.where(sigma > 0.0, _eps_from_total_rdp(rdp, delta), jnp.inf)


@jax.jit
def _epsilon_schedule_grid(sigma, q, round_grid, delta):
    """Cumulative epsilon after each round in `round_grid` (scalars in)."""
    rdp = _rdp_all_orders(sigma, q)                           # (A,)
    total = round_grid[:, None] * rdp[None, :]                # (T, A)
    eps = _eps_from_total_rdp(
        total, jnp.broadcast_to(delta, round_grid.shape))
    return jnp.where(sigma > 0.0, eps, jnp.inf)


def _validate(sample_frac, rounds, delta) -> None:
    sample_frac = np.asarray(sample_frac, dtype=np.float64)
    if np.any(sample_frac <= 0.0) or np.any(sample_frac > 1.0):
        raise ValueError(
            f"sample_frac must be in (0, 1], got {sample_frac}")
    rounds = np.asarray(rounds)
    if np.any(rounds < 1):
        raise ValueError(f"rounds must be >= 1, got {rounds}")
    delta = np.asarray(delta, dtype=np.float64)
    if np.any(delta <= 0.0) or np.any(delta >= 1.0):
        raise ValueError(f"delta must be in (0, 1), got {delta}")


def epsilon_spent(noise_multiplier, sample_frac=1.0, rounds=1,
                  delta=1e-5):
    """Composed (epsilon, delta)-DP cost of `rounds` subsampled-Gaussian
    releases at noise `noise_multiplier` and sampling rate `sample_frac`.

    All four arguments broadcast, so a whole (sigma, q, T) sweep prices in
    one vectorized call; scalars in -> Python float out.  Zero noise costs
    epsilon = +inf.
    """
    _validate(sample_frac, rounds, delta)
    nm = np.asarray(noise_multiplier, dtype=np.float64)
    if np.any(nm < 0.0):
        raise ValueError(f"noise_multiplier must be >= 0, got {nm}")
    args = np.broadcast_arrays(
        nm, np.asarray(sample_frac, dtype=np.float64),
        np.asarray(rounds, dtype=np.float64),
        np.asarray(delta, dtype=np.float64))
    with jax.enable_x64(True):
        out = np.asarray(_epsilon_spent_grid(*args))
    return float(out) if out.ndim == 0 else out


def epsilon_schedule(noise_multiplier, sample_frac=1.0, rounds=1,
                     delta=1e-5) -> np.ndarray:
    """(rounds,) cumulative epsilon spent after rounds 1..rounds.

    The per-round trajectory `StochasticCodedFL.report_extras` surfaces on
    `TraceReport.extras["epsilon_schedule"]`.  Scalar arguments only (one
    strategy's accounting; sweeps vectorize through `epsilon_spent`).
    """
    _validate(sample_frac, rounds, delta)
    nm = float(noise_multiplier)
    if nm < 0.0:
        raise ValueError(f"noise_multiplier must be >= 0, got {nm}")
    grid = np.arange(1, int(rounds) + 1, dtype=np.float64)
    with jax.enable_x64(True):
        out = np.asarray(_epsilon_schedule_grid(
            np.float64(nm), np.float64(sample_frac), grid,
            np.float64(delta)))
    return out
