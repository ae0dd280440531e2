"""CodedFedL: coded federated learning for non-linear regression /
classification in multi-access edge computing (arXiv:2007.03273,
reproduced on the source paper's substrate).

Two ideas ride on the CFL machinery:

  1. **Kernel embedding.**  Each client pushes its raw inputs through a
     shared random-Fourier-feature map (`repro.data.rff_map`) and runs
     LINEAR regression in the `d_feat`-wide feature space — the coded
     parity construction, Eq.-17 weighting, and deadline-`t*` epochs all
     apply unchanged because the learning problem is still least squares.
     `d_feat=None` skips the map entirely and the strategy degenerates to
     `CodedFL` bit-for-bit (same plan, same encoding draws, same arrival
     stream).

  2. **MEC delay model.**  Uplinks traverse a multi-access edge network,
     so the communication leg is a shifted exponential (shift `2 tau`,
     rate `(1-p)/(2 tau p)` — same minimum and mean as the base
     geometric-retransmission model) rather than a retransmission
     mixture.  The load allocation solves on `repro.plan`'s grid solver
     with `PlanRequest.mec_comm=True`: expected returns use the
     closed-form two-exponential convolution CDF, and the Eq.-17 weights
     see the same probabilities via `core.delay_model.mec_total_cdf`.
     Wall-clock epochs sample the same model (`sample_epoch_totals`
     with `mec=True`, draw for draw `sample_total_mec`).

The classification recipe (paper §V): labels from
`repro.data.classification_dataset`, one-vs-rest ±1 targets via
`repro.data.one_vs_rest_targets`, `TrainData.beta_true` a feature-space
reference head so the NMSE trace measures distance to the kernel
regressor (the engine trains in `data.model_dim = d_feat` dimensions
while `data.xs` keeps the raw width `d`).

Parity oracle: `repro.plan.reference_schemes.solve_codedfedl_reference`.
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, ClassVar, Dict, Hashable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.strategy import EpochSchedule, TrainData, coded_epoch_schedule
from repro.core import aggregation, cfl
from repro.core.redundancy import RedundancyPlan
from repro.data.rff import rff_map

from .base import CodedSchemeState

if TYPE_CHECKING:  # annotation-only: keeps schemes free of sim imports
    from repro.serving.scheduler import ConvergenceCriterion
    from repro.sim.network import FleetSpec

# fold_in tweak for deriving the feature-map key from the strategy key;
# far outside encode_fleet's split(key, n) child range for any real fleet
_RFF_FOLD = 0x52FF


@dataclasses.dataclass
class CodedFedLState(CodedSchemeState):
    """`CodedSchemeState` + the client-resident feature tensor.

    features: (n, ell, d_feat) RFF embeddings (aliases `data.xs` when the
    feature map is the identity) — the matrices the engine trains on.
    """

    features: jax.Array


@dataclasses.dataclass(frozen=True)
class CodedFedL:
    """CodedFedL (arXiv:2007.03273): RFF kernel regression + MEC delays.

    key:        PRNG key for the one-time private generator matrices
    d_feat:     random-Fourier-feature width (even, >= 2); None = identity
                map, degenerating to `CodedFL` bit-for-bit
    rff_key:    PRNG key for the shared feature map (derived from `key`
                when omitted — all clients must draw the SAME map)
    rff_gamma:  Gaussian-kernel bandwidth of the feature map
    mec_comm:   use the MEC shifted-exponential communication model for
                the load solve and epoch sampling; None = `d_feat` set
    fixed_c / c_up / include_upload_delay / server_always_returns /
    use_kernel / generator / redundancy_plan: as in `CodedFL`
    """

    key: jax.Array
    d_feat: Optional[int] = None
    rff_key: Optional[jax.Array] = None
    rff_gamma: float = 1.0
    mec_comm: Optional[bool] = None
    fixed_c: Optional[int] = None
    c_up: Optional[int] = None
    include_upload_delay: bool = True
    server_always_returns: bool = False
    use_kernel: bool = False
    generator: str = "normal"
    label: str = "cfedl"
    redundancy_plan: Optional[RedundancyPlan] = None
    grad_path: str = aggregation.FUSED

    def _grad_path(self) -> str:
        return aggregation.resolve_grad_path(self.grad_path,
                                             self.use_kernel)

    # knobs that only shape the plan, host-side sampling, or operand
    # VALUES (rff_gamma moves feature values, never shapes); d_feat stays
    # keyed — it sets the operand widths the engine is traced at
    engine_value_fields: ClassVar[frozenset] = frozenset(
        {"fixed_c", "c_up", "include_upload_delay", "server_always_returns",
         "generator", "mec_comm", "rff_gamma"})
    # y and row ids are pure functions of the TrainData; x is NOT — it
    # depends on the per-strategy feature map — so it stays per-lane
    data_device_keys: ClassVar[frozenset] = frozenset({"y", "row_client"})

    def __post_init__(self):
        if self.d_feat is not None and (self.d_feat < 2 or self.d_feat % 2):
            raise ValueError(
                f"d_feat must be an even integer >= 2, got {self.d_feat}")

    # -- feature map --------------------------------------------------------

    def _mec(self) -> bool:
        if self.mec_comm is None:
            return self.d_feat is not None
        return bool(self.mec_comm)

    def _feature_key(self) -> jax.Array:
        if self.rff_key is not None:
            return self.rff_key
        return jax.random.fold_in(self.key, _RFF_FOLD)

    def features(self, data: TrainData) -> jax.Array:
        """The (n, ell, d_feat) training matrices: RFF embeddings of the
        raw inputs, or `data.xs` itself for the identity map."""
        if self.d_feat is None:
            return data.xs
        return rff_map(data.xs, self.d_feat, self._feature_key(),
                       gamma=self.rff_gamma)

    # -- planning (batched through repro.plan) ------------------------------

    def plan_request(self, fleet: "FleetSpec", data: TrainData):
        """The MEC redundancy problem `plan` would solve."""
        from repro.plan import PlanRequest
        return PlanRequest(edge=fleet.edge, server=fleet.server,
                           data_sizes=np.full(data.n, data.ell,
                                              dtype=np.int64),
                           c_up=self.c_up, fixed_c=self.fixed_c,
                           mec_comm=self._mec())

    def plan_with(self, fleet: "FleetSpec", data: TrainData,
                  plan: Optional[RedundancyPlan]) -> CodedFedLState:
        phi = self.features(data)
        st = cfl.setup(self.key, phi, data.ys, fleet.edge, fleet.server,
                       fixed_c=self.fixed_c, c_up=self.c_up,
                       generator=self.generator, use_kernel=self.use_kernel,
                       plan=plan if plan is not None
                       else self._solve(fleet, data))
        return CodedFedLState(plan=st.plan, load_mask=st.load_mask,
                              x_parity=st.x_parity, y_parity=st.y_parity,
                              edge=fleet.edge, server=fleet.server,
                              features=phi)

    def _solve(self, fleet: "FleetSpec",
               data: TrainData) -> RedundancyPlan:
        from repro.plan import solve_redundancy_batched
        return solve_redundancy_batched([self.plan_request(fleet, data)])[0]

    def plan(self, fleet: "FleetSpec", data: TrainData) -> CodedFedLState:
        return self.plan_with(fleet, data, self.redundancy_plan)

    # -- epoch sampling -----------------------------------------------------

    def sample_epochs(self, state: CodedFedLState, fleet: "FleetSpec",
                      epochs: int, rng: np.random.Generator) -> EpochSchedule:
        # MEC epochs draw from the shifted-exponential model the solve
        # optimized; the base model keeps the degenerate path bit-equal
        # to CodedFL's arrival stream
        return coded_epoch_schedule(
            state, fleet, epochs, rng,
            server_always_returns=self.server_always_returns,
            include_upload_delay=self.include_upload_delay,
            mec=self._mec())

    # -- engine hooks -------------------------------------------------------

    def device_state(self, state: CodedFedLState,
                     data: TrainData) -> Dict[str, jax.Array]:
        d_feat = int(state.features.shape[-1])
        if self._grad_path() == aggregation.FUSED:
            # packed layout over the FEATURE matrices: kernel-regression
            # sessions ride the same fused path as raw CFL.  The reshape
            # is memoized on the state so `fused_coded_device_state`'s
            # identity-keyed operand cache hits on repeated runs.
            x_flat = getattr(state, "_features_flat", None)
            if x_flat is None:
                x_flat = state.features.reshape(data.m, d_feat)
                state._features_flat = x_flat
            return cfl.fused_coded_device_state(state, data, x=x_flat)
        # `cfl.coded_device_state` with x swapped for the feature tensor
        # (identical arrays when the map is the identity)
        n, ell = data.n, data.ell
        row_client = jnp.repeat(jnp.arange(n, dtype=jnp.int32), ell)
        return {"x": state.features.reshape(data.m, d_feat),
                "y": data.ys.reshape(data.m),
                "w_sys": state.load_mask.reshape(data.m),
                "row_client": row_client,
                "x_parity": state.x_parity,
                "y_parity": state.y_parity}

    def round_contributions(self, state, dev, beta, arrivals):
        if self._grad_path() == aggregation.FUSED:
            x, y, w0, client = aggregation.fused_sys_block(dev)
            w = w0 * arrivals["received"][client]
            if state.c == 0:
                return aggregation.round_gradient(
                    x, y, beta, w=w, path=aggregation.FUSED)
            return aggregation.fused_coded_gradient(
                dev, w, arrivals["parity_ok"], beta)
        resid = dev["x"] @ beta - dev["y"]
        w = dev["w_sys"] * arrivals["received"][dev["row_client"]]
        g_sys = (resid * w) @ dev["x"]
        if state.c == 0:  # delta = 0 degenerates to uncoded FL w/ deadline
            return g_sys
        g_par = aggregation.parity_gradient(
            dev["x_parity"], dev["y_parity"], beta,
            use_kernel=self.use_kernel)
        return g_sys + arrivals["parity_ok"] * g_par

    def tiered_contributions(self, state, dev, beta, arrivals, tier_masks):
        # systematic feature-space partials reduce per edge tier; the
        # parity gradient is server-resident and rides as the server term
        if self._grad_path() == aggregation.FUSED:
            x, y, w0, client = aggregation.fused_sys_block(dev)
            masks = aggregation.fused_tier_masks(dev, tier_masks)
            w = w0 * arrivals["received"][client]
            partials = aggregation.tiered_round_gradient(
                x, y, beta, w, masks, path=aggregation.FUSED)
            if state.c == 0:
                return partials, None
            g_par = aggregation.gram_parity_gradient(
                dev["par_gram"], dev["par_gramy"], beta, dev["par_c"])
            return partials, arrivals["parity_ok"] * g_par
        resid = dev["x"] @ beta - dev["y"]
        w = dev["w_sys"] * arrivals["received"][dev["row_client"]]
        partials = aggregation.tier_reduce(resid * w, dev["x"], tier_masks)
        if state.c == 0:
            return partials, None
        g_par = aggregation.parity_gradient(
            dev["x_parity"], dev["y_parity"], beta,
            use_kernel=self.use_kernel)
        return partials, arrivals["parity_ok"] * g_par

    def uplink_bits(self, state: CodedFedLState, fleet: "FleetSpec",
                    epochs: int) -> float:
        # parity shards are (c, d_feat + 1): encoding happens in feature
        # space, so the one-time upload is priced at the feature width
        return cfl.coded_uplink_bits(state, fleet, epochs)

    def engine_key(self, state: CodedFedLState) -> Hashable:
        return (state.c > 0, self.use_kernel, self.d_feat,
                self._grad_path())

    def sweep_inputs(self, state: CodedFedLState, fleet: "FleetSpec",
                     epochs: int, rng: np.random.Generator) -> EpochSchedule:
        """One sweep lane's inputs: `received (epochs, n)` and
        `parity_ok (epochs,)` stack across lanes sharing the fleet size;
        draws are exactly `sample_epochs` (upload first, then the
        per-epoch edge/server stream), so identity-map lanes stay
        bit-equal to `CodedFL` lanes."""
        return self.sample_epochs(state, fleet, epochs, rng)

    def serve_convergence(self, state: CodedFedLState,
                          criterion: "ConvergenceCriterion"):
        """Kernel-regression NMSE plateaus at the RFF approximation floor
        rather than reaching an absolute target, so a serving lane with
        no plateau clause would burn its whole epoch budget; arm a tight
        relative-plateau exit when the user left it off."""
        if self.d_feat is None or criterion.rel_delta is not None:
            return criterion
        return dataclasses.replace(criterion, rel_delta=1e-4)

    def report_extras(self, state: CodedFedLState) -> Dict[str, float]:
        return {"d_feat": float(self.d_feat or 0),
                "rff_gamma": float(self.rff_gamma),
                "mec_comm": float(self._mec()),
                "t_star": float(state.plan.t_star)}
