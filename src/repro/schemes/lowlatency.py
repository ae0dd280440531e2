"""Low-latency coded federated learning over wireless edge networks
(arXiv:2011.06223, reproduced on the source paper's substrate).

The scenario: heterogeneous wireless links — per-device rates tau_i AND
erasure probabilities p_i differ (`sim.network.wireless_fleet`) — and
devices upload PARTIAL work: an assignment of ell points goes out in
`chunks` incremental uploads, chunk q covering the first q*ell/chunks
points, so a straggler that finishes only half its load still contributes
half a gradient instead of nothing.

The joint load-allocation + deadline solve runs on `repro.plan`'s grid
solver with `edge_chunks = chunks`: a device's expected return is

    E[R_i(t; ell)] = (ell/Q) * sum_q Pr{chunk q done by t}

(the partial-return objective), evaluated on the same (t_grid, n, L)
tensor — Q shifted copies of the base CDF grid — so a whole
link-heterogeneity sweep still solves in ONE jitted call.  Over-assignment
stays costly because the stochastic compute rate is mu/ell (the
memory-access slowdown scales with the full assignment), which is what
makes the allocation a real argmax rather than "assign everything".

Eq. 17 generalizes per chunk: the systematic rows of chunk q are encoded
with weight sqrt(1 - Pr{chunk q done by t*}), so parity compensates
exactly the expected shortfall of each chunk.

Parity oracle: `repro.plan.reference_schemes.solve_lowlatency_reference`.
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, ClassVar, Dict, Hashable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.strategy import EpochSchedule, TrainData
from repro.core import aggregation, encoding
from repro.core.delay_model import partial_cdf, sample_total
from repro.core.redundancy import RedundancyPlan

from .base import (CodedSchemeState, coded_uplink_bits,
                   fused_coded_device_state, sample_parity_upload_time)

if TYPE_CHECKING:  # annotation-only: keeps schemes free of sim imports
    from repro.sim.network import FleetSpec


def row_chunks(loads: np.ndarray, ell: int, chunks: int) -> np.ndarray:
    """(n, ell) chunk index of every row: row j < ell_i belongs to chunk
    floor(j * Q / ell_i); rows at or beyond the load get `chunks` (a chunk
    id that never completes, so they can only be covered by parity)."""
    j = np.arange(ell)[None, :]                       # (1, ell)
    ell_i = np.maximum(loads[:, None], 1)             # (n, 1)
    q = (j * chunks) // ell_i
    return np.where(j < loads[:, None], q, chunks).astype(np.int32)


@dataclasses.dataclass
class LowLatencyState(CodedSchemeState):
    """`CodedSchemeState` + per-chunk completion probabilities at t*."""

    chunk_probs: np.ndarray   # (n, Q) Pr{chunk q done by t*}
    row_chunk: np.ndarray     # (n, ell) chunk id per row (Q = punctured)


@dataclasses.dataclass(frozen=True)
class LowLatencyCFL:
    """Partial-return CFL for heterogeneous wireless fleets.

    key:    PRNG key for the one-time private generator matrices
    chunks: incremental uploads per device per epoch (1 = all-or-nothing,
            which degenerates to `CodedFL` bit-for-bit)
    fixed_c / c_up / include_upload_delay / generator: as in `CodedFL`
    redundancy_plan: pre-solved plan (one element of a batched sweep)
    """

    key: jax.Array
    chunks: int = 8
    fixed_c: Optional[int] = None
    c_up: Optional[int] = None
    include_upload_delay: bool = True
    generator: str = "normal"
    label: str = "lowlat"
    redundancy_plan: Optional[RedundancyPlan] = None

    # all knobs (chunks included) reach the traced engine only through
    # operand values — sys_chunk ids, chunks_done counts, the plan — so
    # a whole chunking/heterogeneity sweep shares one compiled engine
    engine_value_fields: ClassVar[frozenset] = frozenset(
        {"chunks", "fixed_c", "c_up", "include_upload_delay", "generator"})
    # data-only operands of the dense layout (one replicated copy per
    # sweep); sys_chunk is plan-derived and stays per-lane
    data_device_keys: ClassVar[frozenset] = frozenset(
        {"x", "y", "row_client"})

    def __post_init__(self):
        if self.chunks < 1:
            raise ValueError(f"chunks must be >= 1, got {self.chunks}")

    # -- planning (batched through repro.plan) ------------------------------

    def plan_request(self, fleet: "FleetSpec", data: TrainData):
        """The partial-return redundancy problem `plan` would solve."""
        from repro.plan import PlanRequest
        return PlanRequest(edge=fleet.edge, server=fleet.server,
                           data_sizes=np.full(data.n, data.ell,
                                              dtype=np.int64),
                           c_up=self.c_up, fixed_c=self.fixed_c,
                           edge_chunks=self.chunks)

    def plan_with(self, fleet: "FleetSpec", data: TrainData,
                  plan: Optional[RedundancyPlan]) -> LowLatencyState:
        if plan is None:
            from repro.plan import solve_redundancy_batched
            plan = solve_redundancy_batched(
                [self.plan_request(fleet, data)])[0]

        n, ell = data.n, data.ell
        q = self.chunks
        # per-chunk Eq. 17: chunk-q rows weighted sqrt(1 - Pr{chunk done});
        # punctured rows (beyond the load) keep weight 1
        probs = partial_cdf(fleet.edge, plan.loads, plan.t_star, q)  # (n, Q)
        rc = row_chunks(plan.loads, ell, q)                       # (n, ell)
        # punctured rows carry chunk id Q, which indexes the appended
        # zero-probability column and therefore gets weight sqrt(1-0) = 1
        probs_ext = np.concatenate([probs, np.zeros((n, 1))], axis=1)
        w_np = np.sqrt(np.maximum(
            0.0, 1.0 - np.take_along_axis(probs_ext, rc, axis=1)))
        weights = jnp.asarray(w_np, dtype=data.xs.dtype)
        load_mask = jnp.asarray(
            np.arange(ell)[None, :] < plan.loads[:, None], dtype=data.xs.dtype)

        if plan.c > 0:
            x_par, y_par = encoding.encode_fleet(
                self.key, data.xs, data.ys, weights, plan.c,
                kind=self.generator)
        else:  # delta = 0 degenerates to uncoded FL with deadline t*
            x_par = jnp.zeros((0, data.d), dtype=data.xs.dtype)
            y_par = jnp.zeros((0,), dtype=data.xs.dtype)

        return LowLatencyState(plan=plan, load_mask=load_mask,
                               x_parity=x_par, y_parity=y_par,
                               edge=fleet.edge, server=fleet.server,
                               chunk_probs=probs, row_chunk=rc)

    def plan(self, fleet: "FleetSpec", data: TrainData) -> LowLatencyState:
        return self.plan_with(fleet, data, self.redundancy_plan)

    # -- epoch sampling -----------------------------------------------------

    def sample_epochs(self, state: LowLatencyState, fleet: "FleetSpec",
                      epochs: int, rng: np.random.Generator) -> EpochSchedule:
        plan = state.plan
        n = fleet.edge.n
        t_star = plan.t_star
        q = self.chunks
        upload_time = sample_parity_upload_time(state, fleet, rng)

        edge = fleet.edge
        loads = plan.loads.astype(np.float64)
        shift = loads * edge.a                               # (n,)
        with np.errstate(divide="ignore", invalid="ignore"):
            scale = np.where(loads > 0, loads / edge.mu, 0.0)
        comm = edge.tau > 0
        p = np.where(comm, edge.p, 0.0)
        fracs = np.arange(1, q + 1, dtype=np.float64) / q     # (Q,)

        chunks_done = np.empty((epochs, n), dtype=np.float32)
        parity_ok = np.ones(epochs, dtype=np.float32)
        for e in range(epochs):
            # component draws mirror `sample_total`'s internal order
            # (exponential, geometric down, geometric up) so chunks = 1
            # reproduces CodedFL's arrival stream exactly
            t_stoch = rng.exponential(1.0, size=n) * scale
            n_d = rng.geometric(1.0 - p, size=n)
            n_u = rng.geometric(1.0 - p, size=n)
            t_comm = np.where(comm, (n_d + n_u) * edge.tau, 0.0)
            t_q = (fracs[None, :] * shift[:, None] + t_stoch[:, None]) \
                + t_comm[:, None]                             # (n, Q)
            chunks_done[e] = np.where(
                loads > 0, np.sum(t_q <= t_star, axis=1), 0.0)
            if state.c > 0:
                t_srv = sample_total(fleet.server, np.array([state.c]),
                                     rng)[0]
                parity_ok[e] = float(t_srv <= t_star)

        return EpochSchedule(
            durations=np.full(epochs, t_star),
            arrivals={"chunks_done": chunks_done, "parity_ok": parity_ok},
            setup_time=upload_time,
            t0=upload_time if self.include_upload_delay else 0.0)

    # -- engine hooks -------------------------------------------------------

    def device_state(self, state: LowLatencyState,
                     data: TrainData) -> Dict[str, jax.Array]:
        # copy: the packed dict is memoized on the state and must not
        # absorb per-strategy extras
        dev = dict(fused_coded_device_state(state, data))
        rc = state.row_chunk.reshape(data.m)
        if "sys_rows" in dev:
            rc = rc[np.asarray(dev["sys_rows"])]
        dev["sys_chunk"] = jnp.asarray(rc)
        return dev

    def _weights(self, dev, arrivals):
        # a row contributes iff its chunk completed by t*
        x, _, w0, client = aggregation.fused_sys_block(dev)
        done = arrivals["chunks_done"][client]
        gate = (dev["sys_chunk"] < done).astype(x.dtype)
        return w0 * gate

    def round_contributions(self, state, dev, beta, arrivals):
        x, y, _, _ = aggregation.fused_sys_block(dev)
        w = self._weights(dev, arrivals)
        if state.c == 0:
            return aggregation.round_gradient(
                x, y, beta, w=w, path=aggregation.FUSED)
        return aggregation.fused_coded_gradient(
            dev, w, arrivals["parity_ok"], beta)

    def tiered_contributions(self, state, dev, beta, arrivals, tier_masks):
        # chunk-gated systematic partials reduce per edge tier; parity is
        # server-resident and rides as the server-side term
        x, y, _, _ = aggregation.fused_sys_block(dev)
        masks = aggregation.fused_tier_masks(dev, tier_masks)
        w = self._weights(dev, arrivals)
        partials = aggregation.tiered_round_gradient(
            x, y, beta, w, masks, path=aggregation.FUSED)
        if state.c == 0:
            return partials, None
        g_par = aggregation.gram_parity_gradient(
            dev["par_gram"], dev["par_gramy"], beta, dev["par_c"])
        return partials, arrivals["parity_ok"] * g_par

    def uplink_bits(self, state: LowLatencyState, fleet: "FleetSpec",
                    epochs: int) -> float:
        # Q incremental chunk packets + 1 completion packet per device-epoch
        return coded_uplink_bits(state, fleet, epochs,
                                 packets_per_epoch=self.chunks + 1)

    def engine_key(self, state: LowLatencyState) -> Hashable:
        return (state.c > 0,)

    def sweep_inputs(self, state: LowLatencyState, fleet: "FleetSpec",
                     epochs: int, rng: np.random.Generator) -> EpochSchedule:
        """One sweep lane's inputs: `chunks_done (epochs, n)` (per-device
        completed-chunk counts) and `parity_ok (epochs,)` stack across
        lanes sharing the fleet size and parity budget; draws are exactly
        `sample_epochs` (component draws mirror `sample_total`'s order, so
        chunks=1 lanes remain bit-equal to CodedFL lanes)."""
        return self.sample_epochs(state, fleet, epochs, rng)

    def report_extras(self, state: LowLatencyState) -> Dict[str, float]:
        return {"chunks": float(self.chunks),
                "mean_chunk_prob": float(np.mean(state.chunk_probs)),
                "t_star": float(state.plan.t_star)}
