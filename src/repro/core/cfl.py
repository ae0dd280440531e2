"""Coded Federated Learning protocol orchestrator (paper §III).

Ties the pieces together in the order the protocol runs:

  1. The server collects delay statistics (a_i, mu_i, tau_i, p_i) and local
     dataset sizes, runs the two-step redundancy optimization (Eqs. 14-16)
     and broadcasts (c, ell*_i, Pr{T_i >= t*}) to the clients.
  2. Each client builds its weight vector (Eq. 17), draws a private G_i and
     uploads parity (G_i W_i X_i, G_i W_i y_i) once.  The server sums them
     into the composite parity dataset.
  3. Per epoch: clients compute partial gradients over their first ell*_i
     points; the server preemptively computes the parity gradient, waits
     until t*, and combines whatever arrived (Eqs. 18-19).

This module holds protocol state and the scan-engine operand layout;
wall-clock behaviour (sampling T_i, deciding who made the deadline) lives
in the strategies' `sample_epochs`, the round itself in their
`round_contributions` (`repro.api.strategy`).
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np

from . import aggregation, encoding
from .delay_model import DeviceDelayParams
from .redundancy import RedundancyPlan, solve_redundancy, systematic_weights

if TYPE_CHECKING:  # annotation-only: core must not import sim at runtime
    from repro.sim.network import FleetSpec


def parity_upload_bits(n: int, c: int, d: int, bits_per_value: int = 32,
                       header_overhead: float = 0.10) -> np.ndarray:
    """Bits each of n clients uploads for its (c, d+1) parity shard — the
    ONE copy of this accounting, shared by every coded scheme's state."""
    per_client = c * (d + 1) * bits_per_value * (1.0 + header_overhead)
    return np.full(n, per_client)


def sample_parity_upload_time(state, fleet: "FleetSpec",
                              rng: np.random.Generator) -> float:
    """One-time parity-upload wall time for any coded-scheme state (needs
    `.parity_upload_bits()` and `.c`): each device ships its shard over its
    own link; devices upload in parallel so the fleet-level delay is the
    slowest one.  The geometric retransmission draw happens even when
    c == 0, preserving the legacy generator order of every entry point."""
    upload_bits = state.parity_upload_bits()
    packets = np.ceil(upload_bits / fleet.packet_bits)
    retrans = rng.geometric(1.0 - fleet.edge.p, size=fleet.edge.n)
    if state.c == 0:
        return 0.0
    return float(np.max(
        packets * retrans * (fleet.packet_bits / fleet.link_rates)))


def coded_uplink_bits(state, fleet: "FleetSpec", epochs: int,
                      packets_per_epoch: int = 2) -> float:
    """Total device->server bits for a coded scheme: the one-time parity
    upload plus `packets_per_epoch` packets per device per epoch (CFL and
    the stochastic scheme use 2; chunked partial uploads pass chunks+1)."""
    n = fleet.edge.n
    return float(np.sum(state.parity_upload_bits())) \
        + epochs * n * packets_per_epoch * fleet.packet_bits


# Packed row counts are padded up to a bucket multiple so sessions with
# nearby plans (e.g. the nu-ladder sweeps) land in the same engine shape
# bucket instead of fragmenting one compiled program per plan.  Padding
# rows replicate row 0 at weight 0.0 — exact-zero contributions, and the
# index stays valid for arrival/tier-mask gathers.
PACK_BLOCK = 512
PACK_MIN = 64
# Above this support density packing is skipped: dropping <15% of rows
# saves almost no bandwidth, while the per-plan packed row count would
# fragment sweeps into one compiled engine per plan AND force the bulk
# (k, d) feature block to be stacked per lane.  The dense fallback keeps
# the full (m, d) rows under the shared data_device_keys names, so every
# dense lane of a sweep shares one engine and ONE replicated copy of X.
PACK_DENSE_FRAC = 0.85


def packed_row_indices(load_flat: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Row indices of the plan's systematic support, bucket-padded.

    load_flat: (m,) flattened load mask.  Returns (idx, valid): int32
    indices of length ceil(k / PACK_BLOCK) * PACK_BLOCK (min PACK_MIN)
    where k rows have load > 0, and the bool validity mask that becomes
    the packed layout's base row weight."""
    keep = np.flatnonzero(np.asarray(load_flat) > 0).astype(np.int32)
    k = int(keep.size)
    target = max(PACK_MIN, PACK_BLOCK * -(-k // PACK_BLOCK)) if k \
        else PACK_MIN
    idx = np.zeros(target, dtype=np.int32)
    idx[:k] = keep
    valid = np.arange(target) < k
    return idx, valid


def parity_gram_factors(state) -> tuple[jax.Array, jax.Array]:
    """Memoized (G, b) = (X~^T X~, y~ X~) for one protocol state — the
    plan-time half of the Gram-folded Eq. 18 (see
    `aggregation.parity_gram`).  Cached on the state instance so every
    engine build over the same plan reuses one factorization."""
    cached = getattr(state, "_parity_gram", None)
    if cached is None:
        cached = aggregation.parity_gram(state.x_parity, state.y_parity)
        state._parity_gram = cached
    return cached


def fused_coded_device_state(state, data, x: jax.Array | None = None,
                             parity_rows: bool = False) -> dict:
    """Scan-engine operands for the FUSED gradient path: systematic rows
    packed to the plan's support (zero-load rows dropped host-side, the
    count bucket-padded at weight 0) and the parity block folded to its
    Gram factors.  At the paper's §IV operating point this cuts the
    per-epoch row stream ~23% and removes both parity passes entirely.

    The packed keys deliberately do NOT overlap the strategies'
    data_device_keys ("x"/"y"/"row_client"): every packed operand is
    plan-derived and must stay per-lane in sweeps.  When the support is
    DENSE (padded count >= PACK_DENSE_FRAC * m) packing is skipped and
    the dict uses the shared names instead — full rows with the load
    mask as `sys_w` — so nu-ladder sweep lanes whose plans load nearly
    everything land in ONE engine bucket with one replicated X (consume
    via `aggregation.fused_sys_block`, which resolves both layouts).

    x: override feature matrix (m, d_feat) — CodedFedL's RFF features.
    parity_rows: also ship the raw parity shards (schemes with dynamic
    per-row parity masks, e.g. StochasticCodedFL at rho < 1, need the
    rows themselves, not just the Gram factors).

    The packed operands are memoized on the state instance (keyed by the
    data/x object identities, which the tuple keeps alive) so repeated
    `Session.run` calls over one plan skip the host-side gathers.
    """
    x_arg = x
    cached = getattr(state, "_fused_dev", None)
    if cached is not None and cached[0] is data and cached[1] is x_arg \
            and cached[2] == parity_rows:
        return cached[3]
    n, ell = data.n, data.ell
    if x is None:
        x = data.xs.reshape(data.m, data.d)
    y = data.ys.reshape(data.m)
    load_flat = np.asarray(state.load_mask).reshape(data.m)
    idx, valid = packed_row_indices(load_flat)
    row_client = np.repeat(np.arange(n, dtype=np.int32), ell)
    if idx.size >= PACK_DENSE_FRAC * data.m:
        # dense fallback: full rows, load mask as the base row weight
        dev = {"x": x, "y": y,
               "row_client": jnp.asarray(row_client),
               "sys_w": jnp.asarray(load_flat, dtype=x.dtype)}
    else:
        jidx = jnp.asarray(idx)
        dev = {"sys_x": jnp.take(x, jidx, axis=0),
               "sys_y": jnp.take(y, jidx),
               "sys_w": jnp.asarray(valid, dtype=x.dtype),
               "sys_client": jnp.asarray(row_client[idx]),
               "sys_rows": jidx}
    if state.c > 0:
        gram, gramy = parity_gram_factors(state)
        dev["par_gram"] = gram
        dev["par_gramy"] = gramy
        # Eq.-18 divisor as an OPERAND: the (d, d) Gram factors erased c
        # from the operand shapes, so one compiled engine serves every
        # parity budget — the divisor must be a value, not a constant
        dev["par_c"] = jnp.asarray(float(state.c), dtype=x.dtype)
        if parity_rows:
            dev["x_parity"] = state.x_parity
            dev["y_parity"] = state.y_parity
    state._fused_dev = (data, x_arg, parity_rows, dev)
    return dev


@dataclasses.dataclass
class CFLState:
    """Frozen protocol state after setup (one-time encoding done)."""

    plan: RedundancyPlan
    weights: jax.Array        # (n, ell) Eq.-17 weight diagonals
    load_mask: jax.Array      # (n, ell) 1.0 on each client's processed points
    x_parity: jax.Array       # (c, d) composite parity features
    y_parity: jax.Array       # (c,)   composite parity labels
    edge: DeviceDelayParams
    server: DeviceDelayParams

    @property
    def c(self) -> int:
        return int(self.x_parity.shape[0])

    def parity_upload_bits(self, bits_per_value: int = 32,
                           header_overhead: float = 0.10) -> np.ndarray:
        """Bits each client uploads for its parity shard (one-time cost)."""
        return parity_upload_bits(self.edge.n, self.c,
                                  int(self.x_parity.shape[1]),
                                  bits_per_value, header_overhead)


def setup(key: jax.Array, xs: jax.Array, ys: jax.Array,
          edge: DeviceDelayParams, server: DeviceDelayParams,
          fixed_c: int | None = None, c_up: int | None = None,
          generator: str = "normal",
          plan: RedundancyPlan | None = None) -> CFLState:
    """Run steps 1-2 of the protocol (optimization + one-time encoding).

    xs: (n, ell, d) client-resident features, ys: (n, ell) labels.
    fixed_c: sweep mode — force the coding redundancy instead of optimizing.
    plan: pre-solved redundancy plan (e.g. one element of a
          `repro.plan.solve_redundancy_batched` sweep); skips the solve and
          runs only the encoding step.
    """
    n, ell, _ = xs.shape
    data_sizes = np.full(n, ell, dtype=np.int64)
    if plan is None:
        plan = solve_redundancy(edge, server, data_sizes,
                                c_up=c_up, fixed_c=fixed_c)

    w_list = systematic_weights(plan, data_sizes)
    weights = jnp.asarray(np.stack(w_list), dtype=xs.dtype)  # (n, ell)
    load_mask = jnp.asarray(
        np.arange(ell)[None, :] < plan.loads[:, None], dtype=xs.dtype)

    if plan.c > 0:
        x_par, y_par = encoding.encode_fleet(
            key, xs, ys, weights, plan.c, kind=generator)
    else:  # delta = 0 degenerates to uncoded FL with deadline t*
        x_par = jnp.zeros((0, xs.shape[-1]), dtype=xs.dtype)
        y_par = jnp.zeros((0,), dtype=xs.dtype)

    return CFLState(plan=plan, weights=weights, load_mask=load_mask,
                    x_parity=x_par, y_parity=y_par, edge=edge, server=server)
