"""Stochastic Coded Federated Learning (arXiv:2201.10092, reproduced on the
source paper's linear-regression + §II-A delay substrate).

SCFL's two departures from the base CFL protocol:

  1. **Privacy noise on the shared coded dataset.**  Each client perturbs
     its parity shard before the one-time upload, so the server-resident
     composite parity is (X~ + N_x, y~ + n_y) with iid Gaussian noise
     calibrated to the coded data's RMS (`noise_multiplier` = noise std /
     coded-entry RMS, i.e. parity SNR ~ 1/noise_multiplier).  Noise buys
     privacy and costs accuracy — the knob is surfaced in
     `TraceReport.extras` via `report_extras`.
  2. **Per-round stochastic parity.**  Each epoch the server samples a
     Bernoulli(`sample_frac`) subset of parity rows and computes the
     (inverse-probability-weighted, hence unbiased) parity gradient on
     that subset only, cutting its per-round compute to rho*c rows.

Both effects discount what one parity row is worth to the aggregate
expected return, so the load-allocation solve runs on `repro.plan`'s grid
solver with `srv_weight = sample_frac / (1 + noise_multiplier^2)` — the
effective-rows factor (a row used with probability rho whose gradient
carries noise power sigma^2 relative to signal contributes rho/(1+sigma^2)
clean rows' worth of information).  Whole noise-level sweeps batch into
ONE jitted solve via `repro.api.plan_sweep` (the requests differ only in
the per-request `(B,)` weight input).

Note a deliberate asymmetry in the plan: `srv_weight` discounts only the
VALUE of the server's rows; the deadline feasibility term still evaluates
Pr{T_srv <= t} at the full parity-row load.  Per-round Bernoulli sampling
can draw close to all c rows, so planning the deadline for the full
budget keeps every realized round feasible — the simulated server
(`sample_epochs`) then draws its completion time at the round's actual
sampled row count, which only lands MORE often than the plan assumed
(conservative, never optimistic).

Parity oracle: `repro.plan.reference_schemes.solve_stochastic_reference` /
`stochastic_noise_scale`.

**Privacy accounting** (`repro.privacy`): the noise knob has quantitative
(epsilon, delta)-DP semantics.  Construct by budget —
`StochasticCodedFL(key=..., epsilon_target=2.0, delta=1e-5, rounds=600)`
— and the smallest adequate `noise_multiplier` is calibrated through the
batched Rényi-DP solve (`repro.privacy.calibrate_noise`); or set
`noise_multiplier` directly and pass `rounds=` to have the spend priced.
Either way `report_extras` surfaces the cumulative per-round trajectory
(`epsilon_schedule`) and the composed total (`epsilon_spent`) on
`TraceReport.extras`.  The accounting model treats each training round as
one release of a Poisson-subsampled Gaussian mechanism at
`(noise_multiplier, sample_frac)` — see the `repro.privacy.accountant`
module docs for the exact order grid and conversion.
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, ClassVar, Dict, Hashable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.strategy import EpochSchedule, TrainData
from repro.core import aggregation, encoding
from repro.core.delay_model import sample_total
from repro.core.redundancy import RedundancyPlan, systematic_weights

from .base import (CodedSchemeState, coded_uplink_bits,
                   fused_coded_device_state, sample_parity_upload_time)

if TYPE_CHECKING:  # annotation-only: keeps schemes free of sim imports
    from repro.sim.network import FleetSpec


@dataclasses.dataclass
class StochasticState(CodedSchemeState):
    """`CodedSchemeState` + the calibrated noise actually injected."""

    noise_scale_x: float
    noise_scale_y: float
    srv_weight: float


@dataclasses.dataclass(frozen=True)
class StochasticCodedFL:
    """SCFL: noisy shared parity + per-round stochastic parity sampling.

    key:              PRNG key for generator matrices AND the privacy noise
    noise_multiplier: privacy-noise std relative to the coded data's RMS
                      (0 = no noise; the paper's privacy/accuracy knob).
                      Defaults to 0.5 when neither it nor `epsilon_target`
                      is given; mutually exclusive with `epsilon_target`.
    sample_frac:      per-round Bernoulli parity-row sampling probability
                      (1 = every row every round; draws NO extra generator
                      randomness at 1, keeping the stream aligned with
                      CodedFL)
    fixed_c / c_up / include_upload_delay / generator: as in `CodedFL`
    redundancy_plan:  pre-solved plan (one element of a batched sweep)
    epsilon_target:   (epsilon, delta)-DP budget to train within; the
                      noise multiplier is then CALIBRATED via
                      `repro.privacy.calibrate_noise` (requires `rounds`).
                      Sweeps should batch the calibration themselves
                      (`repro.plan.srv_weight_for_epsilon` or a vector
                      `calibrate_noise` call) and pass `noise_multiplier=`
                      per strategy — per-strategy calibration here solves
                      one target at a time.
    delta:            DP delta for accounting/calibration
    rounds:           accounting horizon (training rounds composed); when
                      set, `report_extras` prices the run and surfaces
                      `epsilon_spent` + the per-round `epsilon_schedule`
    """

    key: jax.Array
    noise_multiplier: Optional[float] = None
    sample_frac: float = 1.0
    fixed_c: Optional[int] = None
    c_up: Optional[int] = None
    include_upload_delay: bool = True
    generator: str = "normal"
    label: str = "scfl"
    redundancy_plan: Optional[RedundancyPlan] = None
    epsilon_target: Optional[float] = None
    delta: float = 1e-5
    rounds: Optional[int] = None

    # noise / budget knobs feed the plan, the encoded values and the DP
    # accounting report — never the traced engine — so a whole
    # noise/epsilon frontier shares ONE compiled sweep engine.
    # sample_frac stays keyed: it is baked into the traced 1/(c*rho).
    engine_value_fields: ClassVar[frozenset] = frozenset(
        {"fixed_c", "c_up", "include_upload_delay", "generator",
         "noise_multiplier", "epsilon_target", "delta", "rounds"})
    # data-only operands of the dense layout (one replicated copy per
    # sweep); the noised parity factors and row weights stay per-lane
    data_device_keys: ClassVar[frozenset] = frozenset(
        {"x", "y", "row_client"})

    def __post_init__(self):
        if not (0.0 < self.sample_frac <= 1.0):
            raise ValueError(
                f"sample_frac must be in (0, 1], got {self.sample_frac}")
        if not (0.0 < self.delta < 1.0):
            raise ValueError(f"delta must be in (0, 1), got {self.delta}")
        if self.rounds is not None and int(self.rounds) < 1:
            raise ValueError(f"rounds must be >= 1, got {self.rounds}")
        if self.epsilon_target is not None:
            if self.rounds is None:
                raise ValueError(
                    "epsilon_target needs rounds=<training rounds>: the "
                    "budget composes over the whole run")
            from repro.privacy import calibrate_noise
            sigma = float(calibrate_noise(
                self.epsilon_target, delta=self.delta, rounds=self.rounds,
                sample_frac=self.sample_frac))
            # Tolerate noise_multiplier == the calibrated value so
            # `dataclasses.replace` on a budget-constructed strategy
            # (which re-runs this hook with BOTH fields populated) works;
            # any other combination is a genuine conflict.
            if self.noise_multiplier is not None \
                    and self.noise_multiplier != sigma:
                raise ValueError(
                    "pass either epsilon_target= (calibrated noise) or "
                    "noise_multiplier= (manual noise), not both; to "
                    "recalibrate after changing the budget fields, pass "
                    "noise_multiplier=None explicitly")
            object.__setattr__(self, "noise_multiplier", sigma)
        elif self.noise_multiplier is None:
            object.__setattr__(self, "noise_multiplier", 0.5)
        if self.noise_multiplier < 0:
            raise ValueError(
                f"noise_multiplier must be >= 0, got {self.noise_multiplier}")

    @property
    def srv_weight(self) -> float:
        """Effective rows per parity row: rho / (1 + sigma^2)."""
        from repro.plan import effective_srv_weight
        return float(effective_srv_weight(self.noise_multiplier,
                                          self.sample_frac))

    # -- planning (batched through repro.plan) ------------------------------

    def plan_request(self, fleet: "FleetSpec", data: TrainData):
        """The weighted-server redundancy problem `plan` would solve."""
        from repro.plan import PlanRequest
        return PlanRequest(edge=fleet.edge, server=fleet.server,
                           data_sizes=np.full(data.n, data.ell,
                                              dtype=np.int64),
                           c_up=self.c_up, fixed_c=self.fixed_c,
                           srv_weight=self.srv_weight)

    def plan_with(self, fleet: "FleetSpec", data: TrainData,
                  plan: Optional[RedundancyPlan]) -> StochasticState:
        if plan is None:
            from repro.plan import solve_redundancy_batched
            plan = solve_redundancy_batched(
                [self.plan_request(fleet, data)])[0]

        n, ell = data.n, data.ell
        data_sizes = np.full(n, ell, dtype=np.int64)
        w_np = np.stack(systematic_weights(plan, data_sizes))   # (n, ell)
        weights = jnp.asarray(w_np, dtype=data.xs.dtype)
        load_mask = jnp.asarray(
            np.arange(ell)[None, :] < plan.loads[:, None], dtype=data.xs.dtype)

        # calibrated noise scale (float64 on host — the NumPy-reference
        # oracle `stochastic_noise_scale` computes the identical expression)
        d = data.d
        w2 = w_np.astype(np.float64) ** 2
        xs64 = np.asarray(data.xs, dtype=np.float64)
        ys64 = np.asarray(data.ys, dtype=np.float64)
        scale_x = self.noise_multiplier * float(
            np.sqrt(np.sum(w2[..., None] * xs64 ** 2) / d))
        scale_y = self.noise_multiplier * float(
            np.sqrt(np.sum(w2 * ys64 ** 2)))

        if plan.c > 0:
            # encode with the raw key (the exact CodedFL generator stream:
            # noise_multiplier = 0, sample_frac = 1 degenerates to CodedFL
            # bit-for-bit); the noise streams are independent fold-ins
            x_par, y_par = encoding.encode_fleet(
                self.key, data.xs, data.ys, weights, plan.c,
                kind=self.generator)
            if self.noise_multiplier > 0:
                dt = data.xs.dtype
                k_nx = jax.random.fold_in(self.key, 1)
                k_ny = jax.random.fold_in(self.key, 2)
                x_par = x_par + jnp.asarray(scale_x, dt) \
                    * jax.random.normal(k_nx, x_par.shape, dtype=dt)
                y_par = y_par + jnp.asarray(scale_y, dt) \
                    * jax.random.normal(k_ny, y_par.shape, dtype=dt)
        else:  # c = 0 degenerates to uncoded FL with deadline t*
            x_par = jnp.zeros((0, d), dtype=data.xs.dtype)
            y_par = jnp.zeros((0,), dtype=data.xs.dtype)

        return StochasticState(plan=plan, load_mask=load_mask,
                               x_parity=x_par, y_parity=y_par,
                               edge=fleet.edge, server=fleet.server,
                               noise_scale_x=scale_x, noise_scale_y=scale_y,
                               srv_weight=self.srv_weight)

    def plan(self, fleet: "FleetSpec", data: TrainData) -> StochasticState:
        return self.plan_with(fleet, data, self.redundancy_plan)

    # -- epoch sampling -----------------------------------------------------

    def sample_epochs(self, state: StochasticState, fleet: "FleetSpec",
                      epochs: int, rng: np.random.Generator) -> EpochSchedule:
        plan = state.plan
        n = fleet.edge.n
        t_star = plan.t_star
        c = state.c
        upload_time = sample_parity_upload_time(state, fleet, rng)

        received = np.empty((epochs, n), dtype=np.float32)
        parity_mask = np.ones((epochs, c), dtype=np.float32)
        parity_ok = np.ones(epochs, dtype=np.float32)
        for e in range(epochs):
            t_i = sample_total(fleet.edge, plan.loads, rng)
            received[e] = (t_i <= t_star) & (plan.loads > 0)
            if c == 0:
                continue
            if self.sample_frac < 1.0:
                parity_mask[e] = rng.random(c) < self.sample_frac
            rows = int(parity_mask[e].sum())
            t_srv = sample_total(fleet.server, np.array([rows]), rng)[0]
            parity_ok[e] = float(t_srv <= t_star)

        return EpochSchedule(
            durations=np.full(epochs, t_star),
            arrivals={"received": received, "parity_mask": parity_mask,
                      "parity_ok": parity_ok},
            setup_time=upload_time,
            t0=upload_time if self.include_upload_delay else 0.0)

    # -- engine hooks -------------------------------------------------------

    def device_state(self, state: StochasticState,
                     data: TrainData) -> Dict[str, jax.Array]:
        # rho < 1 keeps the raw parity rows alongside the Gram factors:
        # the per-round Bernoulli mask needs the rows
        return fused_coded_device_state(
            state, data, parity_rows=self.sample_frac < 1.0)

    def round_contributions(self, state, dev, beta, arrivals):
        x, y, w0, client = aggregation.fused_sys_block(dev)
        w = w0 * arrivals["received"][client]
        if state.c == 0:
            return aggregation.round_gradient(
                x, y, beta, w=w, path=aggregation.FUSED)
        if self.sample_frac < 1.0:
            # inverse-probability row weights keep the subsampled parity
            # gradient unbiased (E[mask/rho] = 1 per row); folding
            # 1/(c*rho) into them lets the systematic and parity streams
            # share ONE fused launch
            w_par = arrivals["parity_mask"] \
                * (arrivals["parity_ok"]
                   / (dev["par_c"] * self.sample_frac))
            return aggregation.coded_round_gradient(
                x, y, w, dev["x_parity"],
                dev["y_parity"], w_par, beta, path=aggregation.FUSED)
        # rho == 1: static parity — the Gram-folded Eq. 18
        return aggregation.fused_coded_gradient(
            dev, w, arrivals["parity_ok"], beta, rho=self.sample_frac)

    def tiered_contributions(self, state, dev, beta, arrivals, tier_masks):
        # systematic partials reduce per edge tier; the stochastic parity
        # gradient is server-resident and rides as the server-side term
        x, y, w0, client = aggregation.fused_sys_block(dev)
        masks = aggregation.fused_tier_masks(dev, tier_masks)
        w = w0 * arrivals["received"][client]
        partials = aggregation.tiered_round_gradient(
            x, y, beta, w, masks, path=aggregation.FUSED)
        if state.c == 0:
            return partials, None
        c_norm = dev["par_c"] * self.sample_frac
        if self.sample_frac < 1.0:
            w_par = arrivals["parity_mask"] \
                * (arrivals["parity_ok"] / c_norm)
            g_par = aggregation.round_gradient(
                dev["x_parity"], dev["y_parity"], beta, w=w_par,
                path=aggregation.FUSED)
        else:
            g_par = arrivals["parity_ok"] \
                * aggregation.gram_parity_gradient(
                    dev["par_gram"], dev["par_gramy"], beta, c_norm)
        return partials, g_par

    def uplink_bits(self, state: StochasticState, fleet: "FleetSpec",
                    epochs: int) -> float:
        return coded_uplink_bits(state, fleet, epochs)

    def engine_key(self, state: StochasticState) -> Hashable:
        # sample_frac is baked into the traced 1/(c*rho) constant
        return (state.c > 0, float(self.sample_frac))

    def sweep_inputs(self, state: StochasticState, fleet: "FleetSpec",
                     epochs: int, rng: np.random.Generator) -> EpochSchedule:
        """One sweep lane's inputs: `received (epochs, n)`,
        `parity_mask (epochs, c)` and `parity_ok (epochs,)` stack across
        lanes sharing the fleet size and parity budget (c is an operand
        shape, so mixed-c sweeps bucket apart); draws are exactly
        `sample_epochs` — a whole noise/epsilon frontier at one budget is
        a single engine bucket."""
        return self.sample_epochs(state, fleet, epochs, rng)

    def serve_convergence(self, state: StochasticState, criterion):
        """Serving-engine hook (`repro.serving`): epsilon-budget
        exhaustion.  With a calibrated (epsilon, delta) budget, every
        round past the accounting horizon overspends the target, so the
        served epoch budget is capped at `rounds` — the lane then frees
        its slot when the budget is spent, and its truncated
        `epsilon_schedule` lands on `TraceReport.extras`."""
        if self.epsilon_target is None or self.rounds is None:
            return criterion
        cap = int(self.rounds) if criterion.max_epochs is None \
            else min(int(criterion.max_epochs), int(self.rounds))
        return dataclasses.replace(criterion, max_epochs=cap)

    def report_extras(self, state: StochasticState) -> Dict[str, float]:
        """The privacy/accuracy knob — and, when an accounting horizon is
        set, the composed (epsilon, delta) spend — on every TraceReport."""
        extras = {"noise_multiplier": float(self.noise_multiplier),
                  "sample_frac": float(self.sample_frac),
                  "srv_weight": float(state.srv_weight),
                  "noise_scale_x": float(state.noise_scale_x),
                  "noise_scale_y": float(state.noise_scale_y)}
        if self.rounds is not None:
            from repro.privacy import epsilon_schedule
            sched = epsilon_schedule(self.noise_multiplier,
                                     self.sample_frac, self.rounds,
                                     self.delta)
            extras["delta"] = float(self.delta)
            extras["accounting_rounds"] = int(self.rounds)
            extras["epsilon_schedule"] = sched   # cumulative, per round
            extras["epsilon_spent"] = float(sched[-1])
            if self.epsilon_target is not None:
                extras["epsilon_target"] = float(self.epsilon_target)
        return extras
