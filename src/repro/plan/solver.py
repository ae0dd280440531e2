"""Vectorized, batched redundancy planning (paper §III-B, Eqs. 14-16).

The two-step optimization finds, per fleet, the integer load allocation
`ell*_i(t)` maximizing each device's expected return and the smallest epoch
deadline `t*` whose aggregate best return reaches the dataset size `m`.  The
legacy stack (`repro.plan.reference`) re-solved every device's load with one
analytic-CDF call per integer load inside a 64-iteration bisection — ~4s for
one §IV plan.  This module replaces it with a closed-over-grid formulation:

  * the full `(t_grid, n, L)` expected-return tensor is evaluated in one
    shot — loads axis, devices axis, and a batch of candidate deadlines all
    at once — so a deadline probe costs one fused tensor expression instead
    of `L` Python-level CDF calls;
  * `t*` is recovered by monotone grid refinement: each round evaluates the
    aggregate best return on a `GRID_POINTS`-wide deadline grid and shrinks
    the bracket by that factor, so the load problem is never re-solved
    per bisection step;
  * everything is batched over fleets: `solve_redundancy_batched` plans a
    whole delta/fleet sweep in ONE jitted call (`(B, n)` delay parameters,
    per-request caps and parity budgets may differ).

The objective is pluggable (the extension point the `repro.schemes`
subsystem builds on).  Two knobs on `PlanRequest` select the evaluator:

  * `srv_weight` scales the server's expected return in the aggregate —
    the stochastic-CFL discount (arXiv:2201.10092): a privacy-noised,
    per-round-subsampled parity row carries `srv_weight` effective rows.
    Only the VALUE is discounted; the server's completion probability is
    still evaluated at the full row load, so the chosen deadline stays
    feasible for every per-round sampling realization (conservative by
    design — see `repro.schemes.stochastic`).  Requests with different
    weights batch together (it is a `(B,)` input); `srv_weight == 1.0` is
    bit-identical to the base CFL objective.
  * `edge_chunks` switches the edge evaluator to the partial-return
    objective of low-latency wireless CFL (arXiv:2011.06223): a device
    assigned `ell` points uploads `Q` incremental chunks, and its expected
    return is `(ell/Q) * sum_q Pr{chunk q done by t}` — evaluated as `Q`
    shifted copies of the same `(t_grid, n, L)` tensor, so over-assignment
    still hurts through the `mu/ell` memory-access slowdown and the load
    allocation stays a nontrivial argmax.  `edge_chunks` is a static shape
    fact, so requests group by `(padded n, edge_chunks)`; `edge_chunks == 1`
    takes the base code path unchanged.
  * `mec_comm` switches the edge evaluator to the multi-access edge
    computing delay model of CodedFedL (arXiv:2007.03273): instead of the
    discrete retransmission mixture, each device's communication leg is a
    SHIFTED EXPONENTIAL (shift `2 tau`, rate `(1 - p) / (2 tau p)` —
    matching the base geometric model's minimum and mean), and the edge
    return is `ell * Pr{T_comp + T_comm <= t}` via the closed-form
    two-exponential convolution.  A static trace-time branch: requests
    group by `(padded n, edge_chunks, mec_comm)`; `mec_comm == False`
    leaves the base evaluator untouched, and devices with `p == 0` or
    `tau == 0` fall back to the deterministic-comm compute CDF exactly.

Numerics: the solver runs in float64 under a scoped `enable_x64` so its
loads/probabilities match the float64 NumPy reference to well below the
integer-argmax tie margin; parity is enforced by `tests/test_plan_solver.py`.

The edge devices use the negative-binomial retransmission mixture with an
adaptive truncation (`_k_terms`; never beyond the reference's `K_MAX`); the
server is modelled without a communication leg (`tau == 0`), which every
fleet in this repo satisfies and `PlanRequest` validates.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.delay_model import (DeviceDelayParams, K_MAX, mec_total_cdf,
                                    total_cdf)
from repro.core.redundancy import RedundancyPlan

GRID_POINTS = 16    # deadline-grid resolution per refinement round
MAX_ROUNDS = 24     # refinement cap: 16^24 of dynamic range, never binding
MAX_DOUBLINGS = 60  # bracket-expansion cap (matches the legacy guard)

# Shape buckets: pad the device and load axes up so randomized workloads hit
# a handful of compiled kernels instead of one per (n, cap) combination.
# Padded devices get cap 0 and contribute exactly 0.0 to the aggregate.
_N_BUCKET = 8
_L_BUCKET = 64


@dataclasses.dataclass(frozen=True)
class PlanRequest:
    """One redundancy-planning problem: a fleet plus a parity budget.

    edge:       delay params of the n client devices
    server:     delay params of the central server (tau == 0 required)
    data_sizes: (n,) local dataset sizes ell_i
    c_up:       max parity rows the server may receive (default: m)
    fixed_c:    force the coding redundancy (delta-sweep mode)
    t_hi:       optional initial deadline bracket override
    srv_weight: effective rows per parity row in the aggregate return
                (stochastic-CFL noise/subsampling discount; 1.0 = base CFL)
    edge_chunks: per-epoch partial-upload chunks per device (low-latency
                wireless objective; 1 = all-or-nothing base CFL)
    mec_comm:   model each device's communication leg as the CodedFedL
                shifted-exponential MEC link instead of the discrete
                retransmission mixture (False = base CFL)
    """

    edge: DeviceDelayParams
    server: DeviceDelayParams
    data_sizes: np.ndarray
    c_up: Optional[int] = None
    fixed_c: Optional[int] = None
    t_hi: Optional[float] = None
    srv_weight: float = 1.0
    edge_chunks: int = 1
    mec_comm: bool = False

    def __post_init__(self):
        object.__setattr__(
            self, "data_sizes", np.asarray(self.data_sizes, dtype=np.int64))
        if not (0.0 <= float(self.srv_weight) <= 1.0):
            raise ValueError(
                f"srv_weight must be in [0, 1], got {self.srv_weight}")
        if int(self.edge_chunks) < 1:
            raise ValueError(
                f"edge_chunks must be >= 1, got {self.edge_chunks}")
        if self.mec_comm and int(self.edge_chunks) > 1:
            raise ValueError(
                "mec_comm models whole-assignment uploads; combining it "
                "with edge_chunks > 1 partial uploads is not defined")
        if self.server.n != 1:
            raise ValueError("server params must describe exactly one device")
        if float(self.server.tau[0]) != 0.0:
            raise ValueError(
                "the grid solver models the server without a communication "
                "leg; got server tau > 0")
        if self.data_sizes.shape != (self.edge.n,):
            raise ValueError(
                f"data_sizes must have shape ({self.edge.n},), "
                f"got {self.data_sizes.shape}")

    @property
    def m(self) -> int:
        return int(self.data_sizes.sum())

    @property
    def server_cap(self) -> int:
        if self.fixed_c is not None:
            return int(self.fixed_c)
        return int(self.c_up) if self.c_up is not None else self.m

    def default_t_hi(self) -> float:
        """Initial bracket: slowest device's mean epoch time at full load."""
        edge_mean = float(np.max(self.edge.mean_total(self.data_sizes)))
        srv_mean = float(self.server.mean_total(
            np.array([self.server_cap]))[0])
        return max(edge_mean, srv_mean) + 1.0


@functools.partial(jax.jit, static_argnames=("search_f32", "edge_chunks",
                                             "mec_comm"))
def _solve_grid(a, mu, tau, p, srv_a, srv_mu, srv_w, caps, srv_cap, target,
                t_hi0, eps_rel, ell_e, ell_s, ks_search, ks_extract,
                mask_search, mask_extract, frac, *, search_f32=True,
                edge_chunks=1, mec_comm=False):
    """Batched grid solve.  All inputs float64 except integer caps.

    a/mu/tau/p: (B, n) edge delay params    srv_a/srv_mu: (B,) server params
    srv_w: (B,) server return weights (1.0 = base CFL objective)
    caps: (B, n) load caps                  srv_cap: (B,) parity budgets
    target: (B,) aggregate-return targets   t_hi0: (B,) initial brackets
    edge_chunks: static partial-return chunk count (1 = all-or-nothing)
    mec_comm: static flag — shifted-exponential MEC communication legs
              (CodedFedL) instead of the retransmission mixture
    ell_e: (L,) edge load grid 0..L-1       ell_s: (Ls,) server load grid
    ks_search:  (K,) retransmission counts for the deadline search (tail
                below ~1e-12: invisible to any eps_rel)
    ks_extract: (K',) counts for the final load/aggregate extraction (tail
                below half an ulp of 1.0: indistinguishable from the
                reference's full series, see _k_terms)
    mask_search/mask_extract: (B, K)/(B, K') 0/1 masks zeroing each row's
                series beyond ITS OWN truncation length — K is sized for
                the batch's worst-case p, and masked terms add exactly 0.0,
                so every request's plan is bit-identical whether it is
                solved alone or batched with higher-p requests
    frac: (T,) refinement fractions

    Return probabilities are NOT extracted here: the Eq.-17 weights
    sqrt(1 - Pr) amplify last-ulp differences when Pr ~ 1, so the host
    re-evaluates `core.delay_model.total_cdf` at the returned (loads, t*) —
    bit-identical to what every downstream consumer computes.

    The deadline search runs in two phases: a float32 scout (the exp-heavy
    hot path at half the memory traffic) followed by a float64 polish that
    re-brackets and re-refines from the scout's answer.  In healthy regimes
    the scout lands within ~1e-6 of the float64 crossing and the polish is
    one cheap verification round; in SATURATING regimes — parity budget so
    small that the aggregate approaches the target only as every CDF
    saturates — float32 saturates its exponentials earlier than float64
    would, so the scout under-estimates t* and the polish does the real
    work.  The final load/aggregate extraction always runs in float64.
    """
    has_comm = tau > 0.0                                        # (B, n)
    load_ok = ell_e[None, None, :] <= caps[..., None]           # (B, n, L)
    s_ok = ell_s[None, :] <= srv_cap[:, None]                   # (B, Ls)

    def _shifted_exp_cdf(gamma_, s_):
        return jnp.where(
            s_ > 0.0,
            -jnp.expm1(-jnp.minimum(gamma_ * jnp.maximum(s_, 0.0), 700.0)),
            0.0)

    def _make_returns(dtype, ks, k_mask):
        """Expected-return evaluators closing over params cast to `dtype`."""
        a_, mu_, tau_, p_ = (x.astype(dtype) for x in (a, mu, tau, p))
        srv_a_, srv_mu_ = srv_a.astype(dtype), srv_mu.astype(dtype)
        srv_w_ = srv_w.astype(dtype)
        ell_e_, ell_s_, ks_ = (x.astype(dtype) for x in (ell_e, ell_s, ks))
        pmf = (ks_ - 1.0) * p_[..., None] ** (ks_ - 2.0) \
            * (1.0 - p_[..., None]) ** 2                        # (B, n, K)
        pmf = pmf * k_mask.astype(dtype)[:, None, :]  # per-row truncation
        shift = ell_e_[None, None, :] * a_[..., None]           # (B, n, L)
        gamma = mu_[..., None] / jnp.maximum(ell_e_, 1.0)       # (B, n, L)
        s_shift = ell_s_[None, :] * srv_a_[:, None]             # (B, Ls)
        s_gamma = srv_mu_[:, None] / jnp.maximum(ell_s_, 1.0)   # (B, Ls)

        # truncated-series mass, accumulated in the same order as the
        # mixture loop below: when every kept CDF term saturates at exactly
        # 1.0 the mixture equals this bitwise, and snapping it to 1.0 makes
        # full saturation exact — which is also where the reference's
        # 64-term float64 sum rounds to 1.0 (the truncation tail is below
        # half an ulp of 1.0, see _k_terms).  The snap applies ONLY where
        # the kept mass really is ~1: for large p even the full K_MAX
        # series drops real mass (the reference plateaus below 1 there and
        # the infeasibility guard depends on us plateauing identically).
        pmf_total = jax.lax.fori_loop(
            0, ks.shape[0], lambda i, acc: acc + pmf[:, :, i],
            jnp.zeros(a.shape, dtype=dtype))                    # (B, n)
        snap_tol = 1e-4 if dtype == jnp.float32 else 1e-13
        snap_ok = pmf_total >= 1.0 - snap_tol                   # (B, n)

        def _load_cdf(t_res):
            """Per-load completion CDF at residual time `t_res` (B, T', n).

            edge_chunks == 1: Pr{the whole assignment ell is done} — the
            base all-or-nothing evaluator, code path unchanged.
            edge_chunks == Q > 1: the partial-return objective — the MEAN
            over q of Pr{chunk q (first q*ell/Q points) is done}, i.e. the
            expected FRACTION of the assignment uploaded by t.  Each chunk
            shifts the deterministic compute by (q/Q)*ell*a while the
            stochastic rate stays mu/ell (the memory-access slowdown scales
            with the full assignment), so over-assignment still hurts.
            Returns (B, T', n, L)."""
            if edge_chunks == 1:
                s = t_res[..., None] - shift[:, None, :, :]   # (B, T', n, L)
                cdf = _shifted_exp_cdf(gamma[:, None], s)
            else:
                def add_q(j, acc):
                    fq = (jnp.asarray(j, dtype) + 1.0) / edge_chunks
                    s = t_res[..., None] - fq * shift[:, None, :, :]
                    return acc + _shifted_exp_cdf(gamma[:, None], s)
                cdf = jax.lax.fori_loop(
                    0, edge_chunks, add_q,
                    jnp.zeros(t_res.shape + (ell_e.shape[0],), dtype=dtype))
                cdf = cdf / edge_chunks
            return jnp.where(ell_e_ > 0.0, cdf,
                             (t_res[..., None] >= 0.0).astype(dtype))

        def edge_returns_mec(t):
            """Masked MEC E[R_i(t; ell)] grid.  t: (B, T') -> (B, T', n, L).

            CodedFedL's delay model: T_comp is the base shifted
            exponential (shift ell*a, rate mu/ell) but the communication
            leg is ALSO a shifted exponential — shift `2 tau` (the
            erasure-free two-way transfer), rate
            `gm = (1 - p) / (2 tau p)`, chosen so the MEC link matches the
            base geometric retransmission model's minimum (2 tau) and mean
            excess (2 tau p / (1 - p)).  The completion CDF is the
            closed-form convolution of the two exponentials at residual
            `u = t - ell*a - 2 tau`:

                F(u) = 1 - (gm e^{-gc u} - gc e^{-gm u}) / (gm - gc)

            with the equal-rate limit `1 - (1 + g u) e^{-g u}` taken where
            the rates collide (within a relative tie margin, so the
            division never amplifies a catastrophic cancellation).
            Devices with `p == 0` or `tau == 0` have a DETERMINISTIC
            communication leg and fall back to the pure compute CDF at the
            same residual — bit-identical to the base evaluator when
            tau == 0 everywhere.  Monotone in t by construction.
            """
            gc = gamma                                          # (B, n, L)
            gm = (1.0 - p_) / jnp.maximum(2.0 * tau_ * p_, 1e-30)  # (B, n)
            gm_l = gm[:, :, None]                               # (B, n, 1)
            u = t[:, :, None, None] - shift[:, None, :, :] \
                - 2.0 * tau_[:, None, :, None]                  # (B,T',n,L)
            up = jnp.maximum(u, 0.0)
            e_c = jnp.exp(-jnp.minimum(gc[:, None] * up, 700.0))
            e_m = jnp.exp(-jnp.minimum(gm_l[:, None] * up, 700.0))
            denom = gm_l - gc                                   # (B, n, L)
            close = jnp.abs(denom) <= 1e-8 * jnp.maximum(gm_l, gc)
            safe = jnp.where(close, jnp.ones((), dtype=dtype), denom)
            f_neq = 1.0 - (gm_l[:, None] * e_c - gc[:, None] * e_m) \
                / safe[:, None]
            gbar = 0.5 * (gm_l + gc)
            arg = jnp.minimum(gbar[:, None] * up, 700.0)
            f_eq = -jnp.expm1(-arg) - arg * jnp.exp(-arg)
            cdf = jnp.where(close[:, None], f_eq, f_neq)
            cdf = jnp.where(u > 0.0, cdf, 0.0)
            # deterministic communication leg: pure compute CDF at u
            det = jnp.logical_or(p_ <= 0.0, tau_ <= 0.0)        # (B, n)
            cdf = jnp.where(det[:, None, :, None],
                            _shifted_exp_cdf(gc[:, None], u), cdf)
            cdf = jnp.where(ell_e_ > 0.0, cdf, (u >= 0.0).astype(dtype))
            return jnp.where(load_ok[:, None], ell_e_ * cdf, -jnp.inf)

        def edge_returns_base(t):
            """Masked E[R_i(t; ell)] grid.  t: (B, T') -> (B, T', n, L)."""
            def add_k(i, acc):
                t_res = t[:, :, None] - ks_[i] * tau_[:, None, :]
                return acc + pmf[:, None, :, i, None] * _load_cdf(t_res)
            mix = jax.lax.fori_loop(
                0, ks.shape[0], add_k,
                jnp.zeros(t.shape + (a.shape[1], ell_e.shape[0]),
                          dtype=dtype))
            mix = jnp.where(
                jnp.logical_and(mix >= pmf_total[:, None, :, None],
                                snap_ok[:, None, :, None]),
                jnp.ones((), dtype=dtype), mix)
            # tau == 0 devices have no retransmission mixture: compute CDF
            nocomm = _load_cdf(
                jnp.broadcast_to(t[:, :, None], t.shape + (a.shape[1],)))
            mix = jnp.where(has_comm[:, None, :, None], mix, nocomm)
            return jnp.where(load_ok[:, None], ell_e_ * mix, -jnp.inf)

        edge_returns = edge_returns_mec if mec_comm else edge_returns_base

        def server_returns(t):
            """Masked weighted server E[R(t; ell)].  (B, T') -> (B, T', Ls).

            The weight srv_w discounts every parity row's contribution to
            the aggregate (1.0 = base CFL, exact multiply-by-one)."""
            s = t[:, :, None] - s_shift[:, None, :]
            cdf = _shifted_exp_cdf(s_gamma[:, None], s)
            cdf = jnp.where(ell_s_ > 0.0, cdf,
                            (t[:, :, None] >= 0.0).astype(cdf.dtype))
            return jnp.where(s_ok[:, None],
                             srv_w_[:, None, None] * ell_s_ * cdf, -jnp.inf)

        def best_agg(t):
            """Aggregate best return.  t: (B, T') -> (B, T')."""
            return edge_returns(t).max(axis=-1).sum(axis=-1) \
                + server_returns(t).max(axis=-1)

        return edge_returns, server_returns, best_agg

    def _search(best_agg, t_lo0, t_hi0_, target_, eps_, frac_, step0_frac):
        """Bracket-expand then grid-refine.  Returns (t_lo, t_hi, feasible).

        Bracket expansion grows t_hi by a per-row step that doubles every
        iteration, starting at `step0_frac * t_hi`.  step0_frac=1 is the
        legacy pure doubling (cold start); the float64 polish passes
        step0_frac=eps so a last-ulp shortfall against the scout's bracket
        costs one eps-sized nudge instead of overshooting to 2x t*.
        """
        agg0 = best_agg(t_hi0_[:, None])[:, 0]

        def b_cond(st):
            _, _, agg, i = st
            return jnp.logical_and(i < MAX_DOUBLINGS, jnp.any(agg < target_))

        def b_body(st):
            t_hi, step, agg, i = st
            need = agg < target_
            t_new = jnp.where(need, t_hi + step, t_hi)
            step = jnp.where(need, 2.0 * step, step)
            agg_new = jnp.where(need, best_agg(t_new[:, None])[:, 0], agg)
            return t_new, step, agg_new, i + 1

        t_hi, _, agg_hi, _ = jax.lax.while_loop(
            b_cond, b_body,
            (t_hi0_, step0_frac * t_hi0_, agg0, jnp.asarray(0)))
        feasible = agg_hi >= target_

        # --- monotone grid refinement on t ---------------------------------
        def _active(t_lo, t_hi):
            wide = (t_hi - t_lo) > eps_ * jnp.maximum(t_hi, 1e-12)
            return jnp.logical_and(wide, feasible)

        def r_cond(st):
            t_lo, t_hi, r = st
            return jnp.logical_and(r < MAX_ROUNDS,
                                   jnp.any(_active(t_lo, t_hi)))

        def r_body(st):
            t_lo, t_hi, r = st
            grid = t_lo[:, None] + frac_[None, :] * (t_hi - t_lo)[:, None]
            grid = grid.at[:, -1].set(t_hi)  # exact upper edge: invariant
            ok = best_agg(grid) >= target_[:, None]
            idx = jnp.argmax(ok, axis=1)  # first grid point over the target
            hi_new = jnp.take_along_axis(grid, idx[:, None], axis=1)[:, 0]
            lo_prev = jnp.take_along_axis(
                grid, jnp.maximum(idx - 1, 0)[:, None], axis=1)[:, 0]
            lo_new = jnp.where(idx == 0, t_lo, lo_prev)
            act = _active(t_lo, t_hi)
            return (jnp.where(act, lo_new, t_lo),
                    jnp.where(act, hi_new, t_hi), r + 1)

        t_lo, t_hi, _ = jax.lax.while_loop(
            r_cond, r_body, (t_lo0, t_hi, jnp.asarray(0)))
        return t_lo, t_hi, feasible

    # --- phase 1: float32 scout --------------------------------------------
    step0 = jnp.ones((), dtype=t_hi0.dtype)
    if search_f32:
        f32 = jnp.float32
        _, _, best_agg32 = _make_returns(f32, ks_search, mask_search)
        lo32, hi32, _ = _search(
            best_agg32, jnp.zeros_like(t_hi0, dtype=f32), t_hi0.astype(f32),
            target.astype(f32), eps_rel.astype(f32), frac.astype(f32),
            jnp.ones((), dtype=f32))
        t_lo0, t_hi0 = lo32.astype(t_hi0.dtype), hi32.astype(t_hi0.dtype)
        step0 = eps_rel.astype(t_hi0.dtype)
    else:
        t_lo0 = jnp.zeros_like(t_hi0)

    # --- phase 2: float64 polish (re-brackets past the scout if needed) ----
    _, _, best_agg = _make_returns(a.dtype, ks_search, mask_search)
    _, t_star, feasible = _search(
        best_agg, t_lo0, t_hi0, target, eps_rel, frac, step0)

    # --- recover loads / aggregate at t* (float64, half-ulp tail) ----------
    edge_returns, server_returns, _ = _make_returns(a.dtype, ks_extract,
                                                    mask_extract)
    ev = edge_returns(t_star[:, None])[:, 0]                    # (B, n, L)
    loads = jnp.argmax(ev, axis=-1)                             # (B, n)
    best = jnp.take_along_axis(ev, loads[..., None], axis=-1)[..., 0]
    sv = server_returns(t_star[:, None])[:, 0]                  # (B, Ls)
    s_load = jnp.argmax(sv, axis=-1)                            # (B,)
    s_best = jnp.take_along_axis(sv, s_load[:, None], axis=1)[:, 0]
    agg = best.sum(axis=-1) + s_best

    return t_star, loads, s_load, agg, feasible


def _bucket(value: int, bucket: int) -> int:
    return max(bucket, -(-value // bucket) * bucket)


def _k_terms(p_max: float, tol: float = 5e-17) -> int:
    """Retransmission terms needed for a < `tol` negative-binomial tail.

    The reference truncates at K_MAX regardless of p; a tail below half an
    ulp of 1.0 makes the truncated series indistinguishable from the full
    one at saturation (see the pmf_total snap in `_solve_grid`) while
    keeping the §IV hot path cheap (p = 0.1 needs 24 terms, not 64).
    """
    ks = np.arange(2, 2 + K_MAX, dtype=np.float64)
    pmf = (ks - 1.0) * p_max ** (ks - 2.0) * (1.0 - p_max) ** 2
    tails = np.cumsum(pmf[::-1])[::-1]
    small = np.flatnonzero(tails < tol)
    k_eff = int(small[0]) + 1 if small.size else K_MAX
    return min(_bucket(k_eff, 8), K_MAX)


def solve_redundancy_batched(requests: Sequence[PlanRequest],
                             eps_rel: float = 1e-3,
                             grid_points: int = GRID_POINTS
                             ) -> list[RedundancyPlan]:
    """Plan a whole sweep of fleets/budgets in one vectorized solve.

    Requests are grouped by (padded device count, edge_chunks, mec_comm);
    each group runs as a single jitted `(B, n)` solve.  Mixed `fixed_c` /
    free-redundancy / `srv_weight` requests batch fine — budget and weight
    are per-request inputs; `edge_chunks` and `mec_comm` change the
    compiled evaluator, so those requests form their own groups.  Raises
    RuntimeError (like the legacy solver) if any request's fleet cannot
    reach its target.
    """
    requests = list(requests)
    plans: list[Optional[RedundancyPlan]] = [None] * len(requests)
    groups: dict[tuple[int, int, bool], list[int]] = {}
    for i, req in enumerate(requests):
        key = (_bucket(req.edge.n, _N_BUCKET), int(req.edge_chunks),
               bool(req.mec_comm))
        groups.setdefault(key, []).append(i)

    frac = np.arange(1, grid_points + 1, dtype=np.float64) / grid_points

    for (n_pad, edge_chunks, mec_comm), idxs in groups.items():
        grp = [requests[i] for i in idxs]
        b = len(grp)

        def pad(vec, fill):
            out = np.full(n_pad, fill, dtype=np.float64)
            out[:vec.shape[0]] = vec
            return out

        a = np.stack([pad(r.edge.a, 1.0) for r in grp])
        mu = np.stack([pad(r.edge.mu, 1.0) for r in grp])
        tau = np.stack([pad(r.edge.tau, 0.0) for r in grp])
        p = np.stack([pad(r.edge.p, 0.0) for r in grp])
        caps = np.stack([pad(r.data_sizes.astype(np.float64), 0.0)
                         for r in grp]).astype(np.int64)
        srv_a = np.array([r.server.a[0] for r in grp])
        srv_mu = np.array([r.server.mu[0] for r in grp])
        srv_w = np.array([float(r.srv_weight) for r in grp])
        srv_cap = np.array([r.server_cap for r in grp], dtype=np.int64)
        target = np.array([float(r.m) for r in grp])
        t_hi0 = np.array([r.t_hi if r.t_hi is not None else r.default_t_hi()
                          for r in grp])

        l_edge = _bucket(int(caps.max()) + 1, _L_BUCKET)
        l_srv = _bucket(int(srv_cap.max()) + 1, _L_BUCKET)
        # per-request truncation lengths, padded to the group max and
        # masked per row: plans are bit-identical solo vs batched
        k_search = [_k_terms(float(r.edge.p.max()), tol=1e-12) for r in grp]
        k_extract = [_k_terms(float(r.edge.p.max())) for r in grp]

        def k_mask(k_effs):
            mask = np.zeros((b, max(k_effs)), dtype=np.float64)
            for j, k_eff in enumerate(k_effs):
                mask[j, :k_eff] = 1.0
            return mask

        # float32 search resolves t* to ~1e-6 relative; honor tighter eps
        # requests by keeping the whole solve in float64
        search_f32 = eps_rel >= 1e-5

        with jax.enable_x64(True):
            out = _solve_grid(
                a, mu, tau, p, srv_a, srv_mu, srv_w, caps, srv_cap, target,
                t_hi0, np.float64(eps_rel),
                np.arange(l_edge, dtype=np.float64),
                np.arange(l_srv, dtype=np.float64),
                np.arange(2, 2 + max(k_search), dtype=np.float64),
                np.arange(2, 2 + max(k_extract), dtype=np.float64),
                k_mask(k_search), k_mask(k_extract), frac,
                search_f32=search_f32, edge_chunks=edge_chunks,
                mec_comm=mec_comm)
            t_star, loads, s_load, agg, feasible = \
                (np.asarray(o) for o in out)

        if not feasible.all():
            bad = np.flatnonzero(~feasible)
            detail = "; ".join(
                f"request {idxs[j]} (of the requests list): target "
                f"{target[j]:.0f}, best achievable {agg[j]:.1f}"
                for j in bad)
            raise RuntimeError(
                "cannot reach the aggregate expected return target — the "
                f"fleet cannot return the points in finite time: {detail}")

        for j, i in enumerate(idxs):
            req = requests[i]
            n = req.edge.n
            c = int(req.fixed_c) if req.fixed_c is not None \
                else int(s_load[j])
            dev_loads = loads[j, :n].astype(np.int64)
            # per-device return probs re-evaluated on the host: bit-identical
            # to every downstream total_cdf consumer (see _solve_grid docs);
            # mec groups read the matching MEC CDF (the server has no comm
            # leg, so its total_cdf is the same compute CDF either way)
            edge_cdf = mec_total_cdf if mec_comm else total_cdf
            p_return = np.append(
                edge_cdf(req.edge, dev_loads, float(t_star[j])),
                total_cdf(req.server, np.array([float(s_load[j])]),
                          float(t_star[j])))
            plans[i] = RedundancyPlan(
                loads=dev_loads,
                c=c,
                t_star=float(t_star[j]),
                p_return=p_return,
                expected_agg=float(agg[j]),
                loads_cap_total=req.m,
            )
    return plans
