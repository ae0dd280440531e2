"""Inverse privacy calibration: epsilon target -> noise multiplier.

`calibrate_noise(epsilon_target, delta, rounds, sample_frac)` finds the
SMALLEST noise multiplier whose composed budget (per
`repro.privacy.accountant.epsilon_spent`) stays within the target — the
knob users actually hold ("train to epsilon 2 at delta 1e-5"), with the
accountant's forward map inverted numerically.

The solve follows `repro.plan.solver._solve_grid`'s shape: epsilon is
strictly decreasing in sigma, so a bracket-expansion phase (doubling
steps) finds a feasible upper end, then monotone grid refinement shrinks
the bracket by `GRID_POINTS` per round until it is `eps_rel`-relative
tight.  Everything is batched: the epsilon evaluation is one fused
(B, S, A, K) tensor expression over the whole sigma grid of every request
at once, so an entire epsilon-sweep (`benchmarks/fig_privacy.py`, or
`repro.plan.srv_weight_for_epsilon` feeding a `plan_sweep`) calibrates in
ONE jitted call.

The returned sigma sits at the bracket's feasible end, so the calibration
is conservative by construction — `epsilon_spent(sigma) <= epsilon_target`
— while the tight bracket keeps the round-trip within 1e-3 relative of
the target (enforced against the float64 NumPy oracle in
tests/test_privacy.py).  Targets below the order grid's achievable floor
(~5e-4 at delta = 1e-5; see `accountant.DEFAULT_ORDERS`) raise
RuntimeError, mirroring the planner's infeasible-fleet contract.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .accountant import (_eps_from_total_rdp, _rdp_all_orders, _validate)

GRID_POINTS = 16    # sigma-grid resolution per refinement round
MAX_ROUNDS = 24     # refinement cap (16^24 of dynamic range)
MAX_DOUBLINGS = 60  # bracket-expansion cap (matches repro.plan.solver)


@jax.jit
def _calibrate_grid(target, delta, rounds, q, sig_hi0, eps_rel, frac):
    """Batched grid-then-polish solve for the minimal feasible sigma.

    target/delta/rounds/q: (B,) float64    sig_hi0: (B,) initial bracket
    eps_rel: scalar relative sigma tolerance    frac: (S,) grid fractions

    Returns (sigma, eps_at_sigma, feasible).  epsilon(sigma) is evaluated
    for a whole (B, S) sigma grid per refinement round — one fused tensor
    expression, never a per-request host loop.
    """
    def eps_at(sig):                                         # (B, S')
        rdp = _rdp_all_orders(sig, q[:, None]) \
            * rounds[:, None, None]
        return _eps_from_total_rdp(rdp, delta[:, None])

    # --- bracket expansion: grow sig_hi until eps(sig_hi) <= target ------
    eps0 = eps_at(sig_hi0[:, None])[:, 0]

    def b_cond(st):
        _, _, eps, i = st
        return jnp.logical_and(i < MAX_DOUBLINGS, jnp.any(eps > target))

    def b_body(st):
        hi, step, eps, i = st
        need = eps > target
        hi_new = jnp.where(need, hi + step, hi)
        step = jnp.where(need, 2.0 * step, step)
        eps_new = jnp.where(need, eps_at(hi_new[:, None])[:, 0], eps)
        return hi_new, step, eps_new, i + 1

    sig_hi, _, eps_hi, _ = jax.lax.while_loop(
        b_cond, b_body, (sig_hi0, sig_hi0, eps0, jnp.asarray(0)))
    feasible = eps_hi <= target

    # --- monotone grid refinement on sigma -------------------------------
    sig_lo = jnp.zeros_like(sig_hi)

    def _active(lo, hi):
        wide = (hi - lo) > eps_rel * jnp.maximum(hi, 1e-30)
        return jnp.logical_and(wide, feasible)

    def r_cond(st):
        lo, hi, r = st
        return jnp.logical_and(r < MAX_ROUNDS, jnp.any(_active(lo, hi)))

    def r_body(st):
        lo, hi, r = st
        grid = lo[:, None] + frac[None, :] * (hi - lo)[:, None]
        grid = grid.at[:, -1].set(hi)  # exact upper edge: invariant
        ok = eps_at(grid) <= target[:, None]
        idx = jnp.argmax(ok, axis=1)   # first feasible grid point
        hi_new = jnp.take_along_axis(grid, idx[:, None], axis=1)[:, 0]
        lo_prev = jnp.take_along_axis(
            grid, jnp.maximum(idx - 1, 0)[:, None], axis=1)[:, 0]
        lo_new = jnp.where(idx == 0, lo, lo_prev)
        act = _active(lo, hi)
        return (jnp.where(act, lo_new, lo),
                jnp.where(act, hi_new, hi), r + 1)

    _, sigma, _ = jax.lax.while_loop(
        r_cond, r_body, (sig_lo, sig_hi, jnp.asarray(0)))
    return sigma, eps_at(sigma[:, None])[:, 0], feasible


def calibrate_noise(epsilon_target, delta=1e-5, rounds=1, sample_frac=1.0,
                    eps_rel: float = 1e-6):
    """Smallest noise multiplier with epsilon_spent <= epsilon_target.

    All four budget arguments broadcast; array targets calibrate a whole
    epsilon-sweep in one batched jitted solve.  Scalars in -> float out.
    Raises RuntimeError when a target sits below the order grid's
    achievable epsilon floor (no finite noise reaches it).
    """
    _validate(sample_frac, rounds, delta)
    tgt = np.asarray(epsilon_target, dtype=np.float64)
    if np.any(tgt <= 0.0):
        raise ValueError(f"epsilon_target must be > 0, got {tgt}")
    args = np.broadcast_arrays(
        tgt, np.asarray(delta, dtype=np.float64),
        np.asarray(rounds, dtype=np.float64),
        np.asarray(sample_frac, dtype=np.float64))
    shape = args[0].shape
    flat = [np.ascontiguousarray(a).reshape(-1) for a in args]
    frac = np.arange(1, GRID_POINTS + 1, dtype=np.float64) / GRID_POINTS

    with jax.enable_x64(True):
        sigma, eps, feasible = (np.asarray(o) for o in _calibrate_grid(
            flat[0], flat[1], flat[2], flat[3],
            np.ones_like(flat[0]), np.float64(eps_rel), frac))

    if not feasible.all():
        bad = np.flatnonzero(~feasible)
        detail = "; ".join(
            f"target epsilon {flat[0][j]:.2e} (delta {flat[1][j]:.0e}, "
            f"rounds {flat[2][j]:.0f}): best achievable {eps[j]:.2e}"
            for j in bad)
        raise RuntimeError(
            "epsilon target below the accountant's achievable floor — no "
            f"finite noise multiplier reaches it: {detail}")

    out = sigma.reshape(shape)
    return float(out) if out.ndim == 0 else out
