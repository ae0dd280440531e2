"""Pallas TPU kernels: one-pass fused ROUND gradients.

The epoch hot loop of every strategy is the masked round gradient

    g = (w * (X beta - y)) @ X

historically computed as two full passes over X (residual, then the
weighted back-contraction).  The kernels here stream each (bm x d)
row-block of X HBM->VMEM exactly once: the block forms its residual
slice on the MXU, applies the row-weight/arrival mask (a traced
operand, so one compiled launch serves every epoch), and immediately
accumulates its d-wide contribution into a VMEM-resident f32
accumulator.  Neither the (m,) residual nor any per-client (n, d)
stack is ever materialized.

Three variants share the block template of `kernels.coded_grad`:

  * `masked_round_gradient`   — the flat hot loop: one weighted block.
  * `coded_round_gradient`    — systematic + parity blocks fused into a
    single launch (grid = sys blocks ++ parity blocks; `pl.when`
    selects which operand a step reads, index maps are clamped so the
    inactive operand's prefetch stays in range).  Per-row parity
    weights absorb the 1/(c*rho) Eq.-18 normalization, so dynamic
    parity-subsampling masks (StochasticCodedFL) ride the same launch.
  * `tier_masked_round_gradient` — the fleet layer's `tier_reduce`:
    grid (blocks, T) with the row-block resident across the inner tier
    axis, one (1, d) accumulator row per tier.  The per-tier expression
    is the flat kernel's `r * w` further scaled by the tier mask, so a
    single-tier topology stays bit-for-bit equal to the flat kernel.

Accumulation order: row-blocks accumulate sequentially in grid order
(TPU grid semantics), each block's contribution being one f32 MXU
contraction over its bm rows.  That is the SAME order for all three
variants at equal block_m, which is what the fleet layer's bit-exact
single-tier contract relies on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_M = 1024
_F32 = jax.lax.Precision.HIGHEST
# Of the 16 MiB of VMEM a v5e kernel gets by default, the double-buffered
# X blocks may take 14 MiB; the rest holds the row operands, the
# accumulator and the (bm, 1) residual.
_X_VMEM_BYTES = 14 * 2**20


def _row_tile(block_m: int, rows: int, d: int, streams: int) -> int:
    """Row tile for `streams` X operands of width d: `block_m`, capped
    so their double buffers fit `_X_VMEM_BYTES` (d padded to whole
    128-lane vregs; the cap stays a multiple of 128, as the (1, bm) row
    blocks need), and never more than the rows there are."""
    lanes = -(-d // 128) * 128
    cap = _X_VMEM_BYTES // (2 * streams * lanes * 4) // 128 * 128
    return min(block_m, max(cap, 128), rows)


def _vary_alike(*operands):
    """Operands cast to vary over every mesh axis any of them varies over
    (inside `jax.shard_map` a kernel's operands and result must agree on
    their varying axes), and that axis set.  A type cast: no data moves."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in operands))
    return vma, tuple(
        jax.lax.pcast(a, tuple(sorted(vma - jax.typeof(a).vma)),
                      to="varying") if vma - jax.typeof(a).vma else a
        for a in operands)


def _accumulate(x, y, w, beta, out_ref):
    """out += ((X beta - y) * w) @ X for one (bm, d) block.

    y, w and beta arrive as (1, bm) / (1, d) rows, so both contractions
    are 2-D matmuls the TPU lowers directly: the residual column
    (bm, d) x (1, d)^T -> (bm, 1), turned into a row, then
    (1, bm) x (bm, d) -> (1, d).  HIGHEST keeps the MXU at f32 on the
    chip (no effect in interpret mode)."""
    r = jax.lax.dot_general(x, beta, (((1,), (1,)), ((), ())),
                            precision=_F32,
                            preferred_element_type=jnp.float32)
    rw = (r.T - y) * w
    contrib = jax.lax.dot_general(rw, x, (((1,), (0,)), ((), ())),
                                  precision=_F32,
                                  preferred_element_type=jnp.float32)
    out_ref[...] += contrib.astype(out_ref.dtype)


def _masked_kernel(x_ref, y_ref, w_ref, beta_ref, out_ref):
    """Grid step i handles rows [i*bm, (i+1)*bm)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    _accumulate(x_ref[...], y_ref[...], w_ref[...], beta_ref[...], out_ref)


def _pad_rows(x, y, w, bm):
    """Zero-pad rows to a block multiple; pad weight 0 => exact-zero
    contribution, so padding never perturbs the accumulated sum."""
    pad = (-x.shape[0]) % bm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        y = jnp.pad(y, (0, pad))
        w = jnp.pad(w, (0, pad))
    return x, y, w


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def masked_round_gradient(x: jax.Array, y: jax.Array, w: jax.Array,
                          beta: jax.Array, block_m: int = DEFAULT_BLOCK_M,
                          interpret: bool = False) -> jax.Array:
    """g = (w * (X beta - y)) @ X with one HBM pass over X.

    x: (M, D), y/w: (M,), beta: (D,).  M is padded to a block multiple
    (padding rides at weight 0).
    """
    m, d = x.shape
    bm = _row_tile(block_m, m, d, streams=1)
    x, y, w = _pad_rows(x, y, w, bm)
    grid = (x.shape[0] // bm,)
    vma, (x, y, w, beta) = _vary_alike(x, y, w, beta)

    out = pl.pallas_call(
        _masked_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i: (i, 0)),      # stream X blocks
            pl.BlockSpec((1, bm), lambda i: (0, i)),      # y slice
            pl.BlockSpec((1, bm), lambda i: (0, i)),      # w slice
            pl.BlockSpec((1, d), lambda i: (0, 0)),       # beta resident
        ],
        out_specs=pl.BlockSpec((1, d), lambda i: (0, 0)),  # accumulator
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32, vma=vma),
        interpret=interpret,
    )(x, y[None, :], w[None, :], beta[None, :])
    return out[0].astype(beta.dtype)


def _coded_kernel(nsb, xs_ref, ys_ref, ws_ref, xp_ref, yp_ref, wp_ref,
                  beta_ref, out_ref):
    """Steps [0, nsb) stream systematic blocks, [nsb, nsb+npb) parity
    blocks; both accumulate into the same (1, d) output."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    beta = beta_ref[...]

    @pl.when(i < nsb)
    def _sys():
        _accumulate(xs_ref[...], ys_ref[...], ws_ref[...], beta, out_ref)

    @pl.when(i >= nsb)
    def _par():
        _accumulate(xp_ref[...], yp_ref[...], wp_ref[...], beta, out_ref)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def coded_round_gradient(x: jax.Array, y: jax.Array, w: jax.Array,
                         x_par: jax.Array, y_par: jax.Array,
                         w_par: jax.Array, beta: jax.Array,
                         block_m: int = DEFAULT_BLOCK_M,
                         interpret: bool = False) -> jax.Array:
    """g_sys + g_par in ONE launch: the systematic and parity row
    streams share the accumulator.  The index maps of the inactive
    operand are clamped to its last block, so every prefetch is in
    range regardless of which `pl.when` branch a step takes.
    """
    m, d = x.shape
    c = x_par.shape[0]
    bm = _row_tile(block_m, max(m, c), d, streams=2)
    x, y, w = _pad_rows(x, y, w, bm)
    x_par, y_par, w_par = _pad_rows(x_par, y_par, w_par, bm)
    nsb = x.shape[0] // bm
    npb = x_par.shape[0] // bm
    last_s = nsb - 1
    kernel = functools.partial(_coded_kernel, nsb)
    vma, (x, y, w, x_par, y_par, w_par, beta) = _vary_alike(
        x, y, w, x_par, y_par, w_par, beta)

    out = pl.pallas_call(
        kernel,
        grid=(nsb + npb,),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i: (jnp.minimum(i, last_s), 0)),
            pl.BlockSpec((1, bm), lambda i: (0, jnp.minimum(i, last_s))),
            pl.BlockSpec((1, bm), lambda i: (0, jnp.minimum(i, last_s))),
            pl.BlockSpec((bm, d), lambda i: (jnp.maximum(i - nsb, 0), 0)),
            pl.BlockSpec((1, bm), lambda i: (0, jnp.maximum(i - nsb, 0))),
            pl.BlockSpec((1, bm), lambda i: (0, jnp.maximum(i - nsb, 0))),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32, vma=vma),
        interpret=interpret,
    )(x, y[None, :], w[None, :],
      x_par, y_par[None, :], w_par[None, :], beta[None, :])
    return out[0].astype(beta.dtype)


def _tier_kernel(x_ref, y_ref, w_ref, masks_ref, beta_ref, out_ref):
    """Grid (i, t): row-block i scaled by tier t's mask slice into the
    t-th accumulator row.  t is the fastest axis, so the (bm, d) block
    stays VMEM-resident across all T tiers, and each output row's first
    visit is at i == 0."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w = w_ref[...] * masks_ref[...]
    _accumulate(x_ref[...], y_ref[...], w, beta_ref[...], out_ref)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def tier_masked_round_gradient(x: jax.Array, y: jax.Array, w: jax.Array,
                               tier_masks: jax.Array, beta: jax.Array,
                               block_m: int = DEFAULT_BLOCK_M,
                               interpret: bool = False) -> jax.Array:
    """(T, d) tier partials: partial[t] = ((w * mask_t) * (X beta - y)) @ X
    with one HBM pass over X shared by all T tiers.

    tier_masks: (T, M) row masks.  With T == 1 and mask == 1.0 the
    per-block expression is bitwise the flat masked kernel's.
    """
    m, d = x.shape
    t = tier_masks.shape[0]
    bm = _row_tile(block_m, m, d, streams=1)
    pad = (-m) % bm
    x, y, w = _pad_rows(x, y, w, bm)
    if pad:
        tier_masks = jnp.pad(tier_masks, ((0, 0), (0, pad)))
    grid = (x.shape[0] // bm, t)
    vma, (x, y, w, tier_masks, beta) = _vary_alike(x, y, w, tier_masks, beta)

    out = pl.pallas_call(
        _tier_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, t: (i, 0)),   # block resident
            pl.BlockSpec((1, bm), lambda i, t: (0, i)),   # over inner t
            pl.BlockSpec((1, bm), lambda i, t: (0, i)),
            pl.BlockSpec((None, 1, bm), lambda i, t: (t, 0, i)),  # tier t
            pl.BlockSpec((1, d), lambda i, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, d), lambda i, t: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((t, 1, d), jnp.float32, vma=vma),
        interpret=interpret,
    )(x, y[None, :], w[None, :], tier_masks[:, None, :], beta[None, :])
    return out[:, 0].astype(beta.dtype)
