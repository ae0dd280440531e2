"""Gradient computation & straggler-masked aggregation (paper §II, §III-D).

Two gradient sources arrive at the server each epoch:

  * systematic partial gradients, computed by client i over its first
    ell*_i local points:  g_i = X_i[:l]^T (X_i[:l] beta - y_i[:l]);
    only the subset with T_i <= t* arrives (mask),
  * the parity gradient the server computes preemptively on the composite
    parity data:  g_par = (1/c) X~^T (X~ beta - y~)            (Eq. 18)
    which approximates sum_i sum_k w_ik^2 x_ik^T (x_ik beta - y_ik).

Their sum is an (approximately) unbiased estimate of the full gradient
X^T (X beta - y) (Eqs. 18-19).  All ops are jit-compatible; the mask is a
traced operand so one compiled step serves every epoch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# The round-gradient dispatchers.  Every strategy computes its round
# gradient through the functions below with `path=FUSED`: on TPU this
# launches the `kernels.round_grad` Pallas family (one HBM sweep over X,
# masks as traced operands).  Off-TPU, where Pallas runs interpreted, the
# fused win comes from the *operand layout* instead — strategies feed
# packed systematic rows and Gram-folded parity (see
# `core.cfl.fused_coded_device_state`) — and the dispatchers evaluate the
# same sums as jnp expressions.  `path=REFERENCE` always takes the jnp
# expressions, on any backend.
#
# Dispatch on `path`/backend is host-side at trace time: no runtime
# branching.
# --------------------------------------------------------------------------

FUSED = "fused"
REFERENCE = "reference"


def _fused_kernels():
    """TPU only: the Pallas round-gradient entry points (None off-TPU)."""
    from repro.kernels.common import on_tpu
    if not on_tpu():
        return None
    from repro.kernels.round_grad import ops as rg_ops
    return rg_ops


def round_gradient(x: jax.Array, y: jax.Array, beta: jax.Array,
                   w: jax.Array | None = None,
                   path: str = REFERENCE) -> jax.Array:
    """g = (w * (X beta - y)) @ X — the flat round gradient.

    The jnp expression contracts the leading (row-major contiguous) axis
    both times; on TPU the fused path computes the same sum in one HBM
    pass."""
    rg_ops = _fused_kernels() if path == FUSED else None
    if rg_ops is not None:
        return rg_ops.masked_round_gradient(x, y, w, beta)
    resid = x @ beta - y
    if w is None:
        return resid @ x
    return (resid * w) @ x


def coded_round_gradient(x: jax.Array, y: jax.Array, w: jax.Array,
                         x_par: jax.Array, y_par: jax.Array,
                         w_par: jax.Array, beta: jax.Array,
                         path: str = REFERENCE) -> jax.Array:
    """Systematic + parity round gradient with per-row parity weights
    (Eq. 18's 1/(c*rho) normalization folded into w_par).  On TPU the
    fused path is a single two-stream Pallas launch."""
    rg_ops = _fused_kernels() if path == FUSED else None
    if rg_ops is not None:
        return rg_ops.coded_round_gradient(x, y, w, x_par, y_par, w_par,
                                           beta)
    g_sys = round_gradient(x, y, beta, w=w)
    g_par = ((x_par @ beta - y_par) * w_par) @ x_par
    return g_sys + g_par


def tiered_round_gradient(x: jax.Array, y: jax.Array, beta: jax.Array,
                          w: jax.Array | None, tier_masks: jax.Array,
                          path: str = REFERENCE) -> jax.Array:
    """(T, d) tier partials of the masked round gradient — the fleet
    layer's edge stage.  jnp: residual once + `tier_reduce`.  Fused
    path on TPU: one pass over X shared by all tiers; the per-tier
    expression matches the flat kernel at T == 1, preserving the
    single-tier bit-exact contract."""
    rg_ops = _fused_kernels() if path == FUSED else None
    if rg_ops is not None:
        return rg_ops.tier_masked_round_gradient(x, y, w, tier_masks, beta)
    resid = x @ beta - y
    contrib = resid if w is None else resid * w
    return tier_reduce(contrib, x, tier_masks)


@jax.jit
def parity_gram(x_par: jax.Array, y_par: jax.Array):
    """Normal-equation factors of the parity block, computed ONCE at
    plan time: G = X~^T X~ (d, d) and b = y~ X~ (d,).  Eq. 18 then
    collapses to (G beta - b) / c — zero passes over the (c, d) parity
    rows per epoch."""
    return x_par.T @ x_par, y_par @ x_par


def gram_parity_gradient(gram: jax.Array, gramy: jax.Array,
                         beta: jax.Array, c_norm) -> jax.Array:
    """(G beta - b) / c_norm == Eq. 18 through precomputed Gram factors."""
    return (gram @ beta - gramy) / c_norm


def fused_sys_block(dev: dict) -> tuple:
    """(x, y, base_w, client_ids) of the fused systematic block.

    Resolves both layouts `core.cfl.fused_coded_device_state` emits:
    the packed one (plan-support rows under per-lane "sys_*" keys) and
    the dense fallback (full rows under the shared "x"/"y"/"row_client"
    names — replicated, not stacked, across sweep lanes — with the load
    mask as the per-lane base weight).  Trace-time dispatch: the layout
    is part of the engine's shape bucket, never a runtime branch."""
    if "sys_x" in dev:
        return (dev["sys_x"], dev["sys_y"], dev["sys_w"],
                dev["sys_client"])
    return dev["x"], dev["y"], dev["sys_w"], dev["row_client"]


def fused_tier_masks(dev: dict, tier_masks: jax.Array) -> jax.Array:
    """(T, m) tier row masks gathered to the fused layout's rows: packed
    layouts select their support columns, the dense fallback uses the
    full-width masks as-is."""
    if "sys_rows" in dev:
        return jnp.take(tier_masks, dev["sys_rows"], axis=1)
    return tier_masks


def fused_coded_gradient(dev: dict, w: jax.Array, parity_gate,
                         beta: jax.Array, rho: float = 1.0) -> jax.Array:
    """The static-parity fused round: packed systematic rows through
    `round_gradient` (one-pass kernel on TPU) + the Gram-folded parity
    matvec, gated by the scalar parity arrival.  Consumes either fused
    device layout of `core.cfl.fused_coded_device_state`.

    The Eq.-18 divisor c rides along as the `par_c` OPERAND (never a
    trace constant): the Gram factors erased c from the operand shapes,
    so engines are shared across parity budgets and the divisor must
    stay a value.  `rho` is an engine-keyed static (StochasticCodedFL's
    sample_frac); rho == 1.0 multiplies exactly."""
    x, y, _, _ = fused_sys_block(dev)
    g_sys = round_gradient(x, y, beta, w=w, path=FUSED)
    g_par = gram_parity_gradient(dev["par_gram"], dev["par_gramy"], beta,
                                 dev["par_c"] * rho)
    return g_sys + parity_gate * g_par


def tier_reduce(contrib: jax.Array, x: jax.Array,
                tier_masks: jax.Array) -> jax.Array:
    """Per-tier weighted reduce: (T, m) row masks × (m,) contrib × (m, d) x
    → (T, d) tier partials (the edge stage of `repro.fleet`'s hierarchy).

    Each tier partial is the FULL-WIDTH masked gemv `(contrib * mask) @ x`:
    masked-out rows contribute exact ±0.0 terms, so the per-row
    accumulation order of the flat contraction is unchanged and each
    partial equals the flat contraction restricted to its tier
    bit-for-bit.  `lax.map` keeps tiers sequential (like the lane
    engine's per-lane map) so the per-tier expression graph is the flat
    graph, merely masked.
    """
    return jax.lax.map(lambda mask: (contrib * mask) @ x, tier_masks)


def cross_tier_combine(tier_partials: jax.Array) -> jax.Array:
    """(T, d) tier partials → (d,) server aggregate.

    The ONLY floating-point reassociation the hierarchy introduces: a
    T-term sequential sum over tiers (fori_loop, matching the order an
    edge→cloud uplink delivers them).  T == 1 is the identity, which is
    what makes a single-tier topology bit-for-bit equal to the flat path.
    """
    def body(t, acc):
        return acc + tier_partials[t]
    return jax.lax.fori_loop(1, tier_partials.shape[0], body,
                             tier_partials[0])


@jax.jit
def gd_update(beta: jax.Array, grad: jax.Array, lr: float, m: int) -> jax.Array:
    """beta <- beta - (mu/m) * grad  (Eq. 3)."""
    return beta - (lr / m) * grad


def nmse(beta_hat: jax.Array, beta_true: jax.Array) -> jax.Array:
    """Normalized mean-square error ||b^ - b||^2 / ||b||^2 (paper §IV)."""
    return jnp.sum((beta_hat - beta_true) ** 2) / jnp.sum(beta_true ** 2)
