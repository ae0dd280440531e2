"""The `Strategy` protocol: pluggable coding schemes for federated training.

A strategy answers two questions the paper's three hand-rolled loops used to
answer in copy-pasted epoch bodies:

  1. `plan(fleet, data)` — one-time host-side setup: load allocation,
     deadline, encoding.  Returns an opaque strategy state.
  2. `round_contributions(state, dev, beta, arrivals)` — given one epoch's
     arrival masks, produce the combined gradient estimate.  This is traced
     once into the `Session`'s `jax.lax.scan` body, so it must be
     jit-compatible and may read ONLY static structure (shapes, flags, the
     redundancy plan) from `state`; every array it consumes must flow in
     through `dev` (per-run device constants from `device_state`, including
     the strategy's preferred layout of the training data) or `arrivals`
     (per-epoch tensors from `sample_epochs`).

All three built-in strategies lay the data out flat — `x: (m, d)`,
`y: (m,)` with per-row client/group indices — so an epoch is two row-major
matvecs: `resid = x @ beta - y` then `(resid * row_weights) @ x`.
Leading-axis contractions are ~10x faster than the per-client batched
einsums on CPU, and the weighting vector is where each scheme's arrival
semantics live.

Between the two sits the delay machinery: `sample_epochs` pre-samples every
epoch's delays/arrivals up front on the host in one pass
(`core.delay_model.sample_epoch_totals`, shape `(epochs, n)`), preserving
the exact draw order of the legacy per-epoch loops so old and new entry
points produce identical traces from the same `np.random.Generator`.

Three first-class implementations ship here:

  * `UncodedFL`        — synchronous FL, wait for every straggler (Eq. 2).
  * `CodedFL`          — the paper's CFL protocol (wraps `core.cfl`).
  * `GradientCodingFL` — fractional-repetition gradient coding
                         (Tandon et al., the paper's ref [5]), previously
                         only reachable through a bespoke script loop.

New coding schemes drop in as one more class — no fourth epoch loop.  The
first two follow-ups (the stochastic and low-latency wireless variants in
PAPERS.md) live in `repro.schemes`; construct any scheme by name via
`repro.api.make_strategy`.
"""
from __future__ import annotations

import dataclasses
from typing import (
    TYPE_CHECKING, Any, ClassVar, Dict, Hashable, Optional, Protocol,
    runtime_checkable)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation, cfl
from repro.core.delay_model import sample_epoch_totals
from repro.core.gradient_coding import GradCodingPlan, make_plan
from repro.core.redundancy import RedundancyPlan

if TYPE_CHECKING:  # annotation-only: avoids the sim -> api -> sim cycle
    from repro.sim.network import FleetSpec


@dataclasses.dataclass(frozen=True)
class TrainData:
    """The decentralized training problem: client-sharded linear regression.

    xs: (n, ell, d) client-resident features
    ys: (n, ell)    client-resident labels
    beta_true: (d,) ground truth (for the NMSE trace only)
    """

    xs: jax.Array
    ys: jax.Array
    beta_true: jax.Array

    @property
    def n(self) -> int:
        return int(self.xs.shape[0])

    @property
    def ell(self) -> int:
        return int(self.xs.shape[1])

    @property
    def d(self) -> int:
        return int(self.xs.shape[2])

    @property
    def m(self) -> int:
        return self.n * self.ell

    @property
    def model_dim(self) -> int:
        """Dimension of the trained model iterate (`beta_true.shape[0]`).

        Equal to `d` for the raw linear-regression workloads; differs when
        the strategy trains in a transformed space (e.g. `CodedFedL`'s
        random-Fourier-feature head, where `xs` holds raw inputs of width
        `d` but the model lives in the `d_feat`-wide feature space)."""
        return int(self.beta_true.shape[0])

    @classmethod
    def linreg(cls, key: jax.Array, n: int, ell: int, d: int,
               noise_std: float = 1.0) -> "TrainData":
        """Paper §IV data: X iid N(0,1), beta ~ N(0,1)^d, y = X beta + z."""
        k1, k2, k3 = jax.random.split(key, 3)
        xs = jax.random.normal(k1, (n, ell, d), dtype=jnp.float32)
        beta = jax.random.normal(k2, (d,), dtype=jnp.float32)
        zs = noise_std * jax.random.normal(k3, (n, ell), dtype=jnp.float32)
        ys = jnp.einsum("nld,d->nl", xs, beta) + zs
        return cls(xs=xs, ys=ys, beta_true=beta)


@dataclasses.dataclass
class EpochSchedule:
    """Pre-sampled per-epoch randomness for one full training run.

    durations: (epochs,) wall time of each epoch (host-side bookkeeping)
    arrivals:  dict of per-epoch tensors, each with leading dim `epochs`;
               becomes the xs of the Session's `lax.scan`
    setup_time: one-time setup wall time to report (0 if none)
    t0:        wall-clock offset at which epoch 0 starts
    """

    durations: np.ndarray
    arrivals: Dict[str, np.ndarray]
    setup_time: float = 0.0
    t0: float = 0.0


@runtime_checkable
class Strategy(Protocol):
    """Pluggable federated-training scheme (see module docstring)."""

    label: str

    def plan(self, fleet: "FleetSpec", data: TrainData) -> Any:
        """One-time host-side setup; returns the strategy state."""
        ...

    def sample_epochs(self, state: Any, fleet: "FleetSpec", epochs: int,
                      rng: np.random.Generator) -> EpochSchedule:
        """Pre-sample every epoch's delays/arrival masks (NumPy, host)."""
        ...

    def device_state(self, state: Any,
                     data: TrainData) -> Dict[str, jax.Array]:
        """Per-run device-resident constants fed to the scan as operands,
        including the strategy's preferred layout of the training data."""
        ...

    def round_contributions(self, state: Any, dev: Dict[str, jax.Array],
                            beta: jax.Array,
                            arrivals: Dict[str, jax.Array]) -> jax.Array:
        """One epoch's combined gradient estimate (jit/scan-traceable)."""
        ...

    def uplink_bits(self, state: Any, fleet: "FleetSpec",
                    epochs: int) -> float:
        """Total device->server bits for a run of `epochs` epochs."""
        ...

    def engine_key(self, state: Any) -> Hashable:
        """Static facts `round_contributions` branches on (cache key part)."""
        ...

    # Optional hooks (looked up with getattr, not part of the protocol):
    #   * report_extras(state) -> dict — scalar knobs/diagnostics copied
    #     onto TraceReport.extras (e.g. StochasticCodedFL's noise knob);
    #   * plan_request(fleet, data) -> repro.plan.PlanRequest and
    #     plan_with(fleet, data, plan) -> state — expose them to let
    #     `api.plan_sweep` batch the strategy's allocation solve with every
    #     other session's into one jitted grid solve;
    #   * sweep_inputs(state, fleet, epochs, rng) -> EpochSchedule — one
    #     sweep lane's per-epoch inputs for `api.run_sweep`.  Contract:
    #     every arrival tensor's shape is a function of the engine-static
    #     structure only (so lanes of one shape bucket stack), and the
    #     generator draw order is identical to `sample_epochs` (so sweep
    #     lanes are bit-for-bit equal to solo runs).  `run_sweep` falls
    #     back to `sample_epochs` when absent;
    #   * engine_value_fields: frozenset of dataclass field names that only
    #     feed operand VALUES (plan inputs, host-side sampling, report
    #     metadata) and never steer the traced engine.  The sweep engine
    #     keys its compiled-engine cache on every OTHER primitive field
    #     (plus `engine_key`), so declaring a field here lets lanes that
    #     differ only in that knob share one compiled engine; omitting a
    #     declaration is always safe, merely over-fragmenting buckets;
    #   * data_device_keys: frozenset of `device_state` keys whose arrays
    #     are pure functions of the TrainData alone (the flat training
    #     matrices, typically).  All lanes of one `run_sweep(sessions,
    #     data)` call see the same data, so the sweep engine ships ONE
    #     replicated copy of these operands instead of stacking them B
    #     times.  Omitting the declaration is always safe (everything is
    #     stacked per lane);
    #   * tiered_contributions(state, dev, beta, arrivals, tier_masks) ->
    #     ((T, d) tier partials, optional (d,) server term) — the
    #     hierarchical form of `round_contributions` consumed by
    #     `repro.fleet.HierarchicalCFL`: given (T, m) one-hot row masks
    #     over the flat client-major layout, return per-tier partials via
    #     `core.aggregation.tier_reduce` (full-width masked gemvs, so each
    #     partial matches the flat contraction bit-for-bit) plus any
    #     server-side term (parity gradients) that is NOT client-resident
    #     and therefore bypasses the edge tier.  Contract:
    #     `cross_tier_combine(partials) + server` must equal
    #     `round_contributions` exactly for a single all-ones tier mask
    #     and to T-term-reassociation ulp for any tier partition.
    #     Strategies without the hook cannot be wrapped hierarchically;
    #   * serve_convergence(state, criterion) -> criterion — the serving
    #     engine's convergence hook (`repro.serving.fed_engine`): given
    #     the engine's per-lane `ConvergenceCriterion`, return a
    #     (possibly tightened) criterion for this session.  The canonical
    #     use is budget exhaustion: `StochasticCodedFL` caps
    #     `max_epochs` at its DP accounting horizon so an
    #     epsilon-budgeted lane exits when the budget is spent instead
    #     of training past it.  Absent the hook, the engine's criterion
    #     applies unchanged.


# ---------------------------------------------------------------------------
# Uncoded synchronous FL
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class UncodedState:
    loads: np.ndarray  # (n,) full local dataset size per client


@dataclasses.dataclass(frozen=True)
class UncodedFL:
    """Synchronous uncoded FL: every epoch waits for all n clients (Eq. 2)."""

    label: str = "uncoded"
    grad_path: str = aggregation.FUSED

    # grad_path steers the traced engine; it stays OUT of
    # engine_value_fields so the engine cache keys on it automatically
    engine_value_fields: ClassVar[frozenset] = frozenset()
    # the flat training matrices are data-only: one replicated copy per sweep
    data_device_keys: ClassVar[frozenset] = frozenset({"x", "y"})

    def plan(self, fleet: "FleetSpec", data: TrainData) -> UncodedState:
        return UncodedState(loads=np.full(data.n, data.ell))

    def sample_epochs(self, state: UncodedState, fleet: "FleetSpec",
                      epochs: int, rng: np.random.Generator) -> EpochSchedule:
        t, = sample_epoch_totals([(fleet.edge, state.loads)], epochs, rng)
        return EpochSchedule(durations=t.max(axis=1),  # wait for everyone
                             arrivals={"epoch": np.zeros(epochs, np.float32)})

    def device_state(self, state: UncodedState,
                     data: TrainData) -> Dict[str, jax.Array]:
        return {"x": data.xs.reshape(data.m, data.d),
                "y": data.ys.reshape(data.m)}

    def round_contributions(self, state, dev, beta, arrivals):
        # exact full gradient (Eq. 2); both grad paths route through the
        # dispatcher — on CPU they are one and the same expression
        return aggregation.round_gradient(
            dev["x"], dev["y"], beta,
            path=aggregation.resolve_grad_path(self.grad_path))

    def tiered_contributions(self, state, dev, beta, arrivals, tier_masks):
        return aggregation.tiered_round_gradient(
            dev["x"], dev["y"], beta, None, tier_masks,
            path=aggregation.resolve_grad_path(self.grad_path)), None

    def uplink_bits(self, state: UncodedState, fleet: "FleetSpec",
                    epochs: int) -> float:
        return epochs * state.loads.shape[0] * 2 * fleet.packet_bits

    def engine_key(self, state: UncodedState) -> Hashable:
        return ()

    def sweep_inputs(self, state: UncodedState, fleet: "FleetSpec",
                     epochs: int, rng: np.random.Generator) -> EpochSchedule:
        """One sweep lane's inputs: the (epochs,) placeholder tensor stacks
        across any uncoded lanes; draws are exactly `sample_epochs`."""
        return self.sample_epochs(state, fleet, epochs, rng)


# ---------------------------------------------------------------------------
# Coded Federated Learning (the paper's protocol)
# ---------------------------------------------------------------------------

def coded_epoch_schedule(state, fleet: "FleetSpec", epochs: int,
                         rng: np.random.Generator, *,
                         server_always_returns: bool,
                         include_upload_delay: bool,
                         mec: bool = False) -> EpochSchedule:
    """The epochs of a coded scheme with deadline t* (`CodedFL`,
    `CodedFedL`): the one-time parity upload, drawn first, then per epoch
    the edge fleet at its loads and, unless the parity always lands
    (`server_always_returns` or c == 0), the server at its c rows.
    `received (epochs, n)` marks the clients with a load back by t*;
    `parity_ok (epochs,)` whether the server made t*."""
    plan = state.plan
    t_star = plan.t_star
    upload_time = cfl.sample_parity_upload_time(state, fleet, rng)
    groups = [(fleet.edge, plan.loads)]
    with_server = not (server_always_returns or state.c == 0)
    if with_server:
        groups.append((fleet.server, np.array([state.c])))
    totals = sample_epoch_totals(groups, epochs, rng, mec=mec)
    received = ((totals[0] <= t_star) & (plan.loads > 0)).astype(np.float32)
    if with_server:
        parity_ok = (totals[1][:, 0] <= t_star).astype(np.float32)
    else:
        parity_ok = np.ones(epochs, dtype=np.float32)
    return EpochSchedule(
        durations=np.full(epochs, t_star),
        arrivals={"received": received, "parity_ok": parity_ok},
        setup_time=upload_time,
        t0=upload_time if include_upload_delay else 0.0)


@dataclasses.dataclass(frozen=True)
class CodedFL:
    """CFL (paper §III): deadline t*, systematic + parity gradients.

    key:        PRNG key for the one-time private generator matrices
    fixed_c:    force the coding redundancy (delta-sweep mode) instead of
                running the Eq. 14-16 optimization
    c_up:       cap on the server's parity budget
    include_upload_delay: charge the one-time parity upload to the clock
    server_always_returns: ablation — parity gradient always lands
    use_kernel: DEPRECATED — folded into grad_path (True forces "fused");
                still routes the one-time parity ENCODE through Pallas
    redundancy_plan: pre-solved `RedundancyPlan` (one element of a
                `repro.plan.solve_redundancy_batched` sweep); `plan` then
                skips the solve and only encodes
    grad_path:  "fused" (default — packed one-pass round gradient, Gram
                parity) or "reference" (the verbatim pre-fusion epoch
                body, the bit-parity oracle)
    """

    key: jax.Array
    fixed_c: Optional[int] = None
    c_up: Optional[int] = None
    include_upload_delay: bool = True
    server_always_returns: bool = False
    use_kernel: bool = False
    generator: str = "normal"
    label: str = "cfl"
    redundancy_plan: Optional["RedundancyPlan"] = None
    grad_path: str = aggregation.FUSED

    def _grad_path(self) -> str:
        return aggregation.resolve_grad_path(self.grad_path,
                                             self.use_kernel)

    # knobs that only shape the plan / host-side sampling, never the traced
    # engine: lanes differing in them share one compiled sweep engine
    # (use_kernel stays keyed — it swaps the parity-gradient code path)
    engine_value_fields: ClassVar[frozenset] = frozenset(
        {"fixed_c", "c_up", "include_upload_delay", "server_always_returns",
         "generator"})
    # data-only operands (one replicated copy per sweep); the plan-derived
    # load mask and parity shards stay per-lane
    data_device_keys: ClassVar[frozenset] = frozenset(
        {"x", "y", "row_client"})

    def plan(self, fleet: "FleetSpec", data: TrainData) -> cfl.CFLState:
        return self.plan_with(fleet, data, self.redundancy_plan)

    # -- batched-planning hooks (see api.session.plan_sweep) ----------------

    def plan_request(self, fleet: "FleetSpec", data: TrainData):
        """The redundancy problem this strategy would solve in `plan`."""
        from repro.plan import PlanRequest
        return PlanRequest(edge=fleet.edge, server=fleet.server,
                           data_sizes=np.full(data.n, data.ell,
                                              dtype=np.int64),
                           c_up=self.c_up, fixed_c=self.fixed_c)

    def plan_with(self, fleet: "FleetSpec", data: TrainData,
                  plan: Optional["RedundancyPlan"]) -> cfl.CFLState:
        """`plan` with the redundancy solve already done (or None to solve)."""
        return cfl.setup(self.key, data.xs, data.ys, fleet.edge, fleet.server,
                         fixed_c=self.fixed_c, c_up=self.c_up,
                         generator=self.generator, use_kernel=self.use_kernel,
                         plan=plan)

    def sample_epochs(self, state: cfl.CFLState, fleet: "FleetSpec",
                      epochs: int, rng: np.random.Generator) -> EpochSchedule:
        return coded_epoch_schedule(
            state, fleet, epochs, rng,
            server_always_returns=self.server_always_returns,
            include_upload_delay=self.include_upload_delay)

    def device_state(self, state: cfl.CFLState,
                     data: TrainData) -> Dict[str, jax.Array]:
        if self._grad_path() == aggregation.FUSED:
            return cfl.fused_coded_device_state(state, data)
        return cfl.coded_device_state(state, data)

    def round_contributions(self, state, dev, beta, arrivals):
        if self._grad_path() == aggregation.FUSED:
            # fused layout (packed support or dense fallback): the base
            # row weight carries the load support, parity is Gram-folded
            x, y, w0, client = aggregation.fused_sys_block(dev)
            w = w0 * arrivals["received"][client]
            if state.c == 0:
                return aggregation.round_gradient(
                    x, y, beta, w=w, path=aggregation.FUSED)
            return aggregation.fused_coded_gradient(
                dev, w, arrivals["parity_ok"], beta)
        resid = dev["x"] @ beta - dev["y"]
        # row weight = (point within client's systematic load) AND
        # (client's partial gradient arrived by t*)
        w = dev["w_sys"] * arrivals["received"][dev["row_client"]]
        g_sys = (resid * w) @ dev["x"]
        if state.c == 0:  # delta = 0 degenerates to uncoded FL w/ deadline
            return g_sys
        g_par = aggregation.parity_gradient(
            dev["x_parity"], dev["y_parity"], beta,
            use_kernel=self.use_kernel)
        return g_sys + arrivals["parity_ok"] * g_par

    def tiered_contributions(self, state, dev, beta, arrivals, tier_masks):
        # systematic partials reduce per edge tier; the parity gradient is
        # computed AT the server on the composite parity data, so it rides
        # as the server-side term and bypasses the tier stage entirely
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

    def uplink_bits(self, state: cfl.CFLState, fleet: "FleetSpec",
                    epochs: int) -> float:
        return cfl.coded_uplink_bits(state, fleet, epochs)

    def engine_key(self, state: cfl.CFLState) -> Hashable:
        return (state.c > 0, self.use_kernel, self._grad_path())

    def sweep_inputs(self, state: cfl.CFLState, fleet: "FleetSpec",
                     epochs: int, rng: np.random.Generator) -> EpochSchedule:
        """One sweep lane's inputs: `received (epochs, n)` and
        `parity_ok (epochs,)` stack across every CFL lane sharing the fleet
        size; draws are exactly `sample_epochs` (upload first, then the
        per-epoch edge/server stream)."""
        return self.sample_epochs(state, fleet, epochs, rng)


# ---------------------------------------------------------------------------
# Gradient coding (Tandon et al., the paper's ref [5])
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class GradCodingState:
    plan: GradCodingPlan
    n_groups: int
    ell: int            # local shard size (each client computes r * ell)
    share_bits: float   # per-client raw-data sharing cost (one-time)
    shard_time: float


@dataclasses.dataclass(frozen=True)
class GradientCodingFL:
    """Fractional-repetition gradient coding with replication factor r.

    Client i holds its whole group's data (r shards) and returns the
    group-sum gradient; an epoch ends when every group has >= 1 returner,
    at which point the server recovers the EXACT full gradient (no LLN
    approximation — contrast with CodedFL).
    """

    r: int
    label: str = "gradcode"
    grad_path: str = aggregation.FUSED

    # r shapes the plan (groups) only; the traced engine sees it through
    # `engine_key` (n_groups) and the arrival/device tensor shapes
    engine_value_fields: ClassVar[frozenset] = frozenset({"r"})
    # the flat matrices are data-only; row_group is plan-derived (per lane)
    data_device_keys: ClassVar[frozenset] = frozenset({"x", "y"})

    def plan(self, fleet: "FleetSpec", data: TrainData) -> GradCodingState:
        plan = make_plan(data.n, self.r)
        n_groups = int(plan.groups.max()) + 1
        # one-time cost: each client receives (r-1) shards of raw data from
        # its group peers (the privacy-relevant transfer CFL avoids)
        share_bits = (self.r - 1) * data.ell * (data.d + 1) * 32 * 1.1
        shard_time = float(np.max(share_bits / fleet.link_rates))
        return GradCodingState(plan=plan, n_groups=n_groups, ell=data.ell,
                               share_bits=share_bits, shard_time=shard_time)

    def sample_epochs(self, state: GradCodingState, fleet: "FleetSpec",
                      epochs: int, rng: np.random.Generator) -> EpochSchedule:
        n = fleet.edge.n
        # each client processes its whole group's data: r * ell points
        loads = np.full(n, state.plan.r * state.ell)
        t_all, = sample_epoch_totals([(fleet.edge, loads)], epochs, rng)
        groups = np.asarray(state.plan.groups)
        per_group = np.full((epochs, state.n_groups), np.inf)
        np.minimum.at(per_group,
                      (np.arange(epochs)[:, None], groups[None, :]), t_all)
        # each epoch ends when the last group's first returner lands
        durations = per_group.max(axis=1)
        group_ok = np.ones((epochs, state.n_groups), dtype=np.float32)
        return EpochSchedule(durations=durations,
                             arrivals={"group_ok": group_ok},
                             setup_time=state.shard_time,
                             t0=state.shard_time)

    def device_state(self, state: GradCodingState,
                     data: TrainData) -> Dict[str, jax.Array]:
        row_group = jnp.repeat(
            jnp.asarray(state.plan.groups, dtype=jnp.int32), data.ell)
        return {"x": data.xs.reshape(data.m, data.d),
                "y": data.ys.reshape(data.m),
                "row_group": row_group}

    def round_contributions(self, state, dev, beta, arrivals):
        # groups with >= 1 returner contribute their exact group-sum
        # gradient (what the coded uploads decode to); with every group
        # reporting this is exactly the full gradient
        w = arrivals["group_ok"][dev["row_group"]]
        return aggregation.round_gradient(
            dev["x"], dev["y"], beta, w=w,
            path=aggregation.resolve_grad_path(self.grad_path))

    def tiered_contributions(self, state, dev, beta, arrivals, tier_masks):
        # every contribution is client-resident (the decoded group sums),
        # so the whole gradient reduces through the edge tiers
        w = arrivals["group_ok"][dev["row_group"]]
        return aggregation.tiered_round_gradient(
            dev["x"], dev["y"], beta, w, tier_masks,
            path=aggregation.resolve_grad_path(self.grad_path)), None

    def uplink_bits(self, state: GradCodingState, fleet: "FleetSpec",
                    epochs: int) -> float:
        n = fleet.edge.n
        return n * state.share_bits + epochs * n * 2 * fleet.packet_bits

    def engine_key(self, state: GradCodingState) -> Hashable:
        return (state.n_groups,)

    def sweep_inputs(self, state: GradCodingState, fleet: "FleetSpec",
                     epochs: int, rng: np.random.Generator) -> EpochSchedule:
        """One sweep lane's inputs: `group_ok (epochs, n_groups)` stacks
        across lanes with equal replication structure (n_groups is in
        `engine_key`, so mixed-r sweeps bucket apart); draws are exactly
        `sample_epochs`."""
        return self.sample_epochs(state, fleet, epochs, rng)
