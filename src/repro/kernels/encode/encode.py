"""Pallas TPU kernels: fused weighted parity encoding P = G (W X)  (Eq. 9).

The client-side one-time encoding multiplies the private generator matrix
G (c x ell) into the weighted local dataset.  The naive form materializes
W X (an ell x d HBM round-trip); the kernel fuses the diagonal scaling into
the matmul's RHS load, so X streams HBM->VMEM once and W X never exists in
HBM.

Tiling: grid (c/bc, d/bd, ell/bl) with an fp32 VMEM accumulator per (bc, bd)
output tile; the contraction dim ell is the innermost (sequential) grid axis
so the accumulator stays resident.  Tile sizes default to MXU-aligned 128s.

Two generator sources:

  * `encode_parity`      — G is an input: sampled on the host PRNG and
    materialized in HBM once per client (the original kernel).
  * `encode_parity_prng` — G never exists in memory AT ALL: each (bc, bl)
    generator tile is (re)generated inside the kernel from the client's
    PRNG key, fused straight into the matmul.  The in-kernel generator is
    counter-based threefry2x32 — the SAME hash, counter layout, and
    bits-to-float path as `jax.random.normal` / `jax.random.rademacher`
    on a legacy uint32 key pair — so the generated G is bit-identical to
    the host-PRNG path and the variant is a drop-in replacement
    (parity-tested in interpret mode against the host path in
    `tests/test_kernels.py`).  `pltpu.prng_random_bits` was considered
    and rejected: its raw bit stream cannot be replayed on the host (so
    no parity oracle) and it has no interpret-mode implementation in this
    JAX; the threefry tile generator below is plain jnp integer math that
    lowers on TPU and interprets on CPU.  `generator_values` exposes the
    tile math as a host-callable oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

DEFAULT_BLOCK = (128, 128, 128)  # (bc, bd, bl)
# f32 passes on the MXU: at the default precision the chip rounds the
# operands to bf16, and parity would drift from the host path by ~1e-2
_F32 = jax.lax.Precision.HIGHEST


def _kernel(g_ref, w_ref, x_ref, out_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    g = g_ref[...]                       # (bc, bl)
    w = w_ref[...]                       # (1, bl)
    x = x_ref[...]                       # (bl, bd)
    xw = x * w[0][:, None].astype(x.dtype)   # fused diagonal scaling
    out_ref[...] += jax.lax.dot(g, xw, precision=_F32,
                                preferred_element_type=jnp.float32
                                ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def encode_parity(g: jax.Array, w: jax.Array, x: jax.Array,
                  block: tuple[int, int, int] = DEFAULT_BLOCK,
                  interpret: bool = False) -> jax.Array:
    """P = G @ (diag(w) X).  g: (C, L), w: (L,), x: (L, D) -> (C, D)."""
    c, ell = g.shape
    ell2, d = x.shape
    assert ell == ell2 and w.shape == (ell,)
    bc, bd, bl = block
    bc, bd, bl = min(bc, c), min(bd, d), min(bl, ell)
    pc, pd, pL = (-c) % bc, (-d) % bd, (-ell) % bl
    if pc or pL:
        g = jnp.pad(g, ((0, pc), (0, pL)))
    if pL or pd:
        x = jnp.pad(x, ((0, pL), (0, pd)))
    if pL:
        w = jnp.pad(w, (0, pL))
    grid = (g.shape[0] // bc, x.shape[1] // bd, g.shape[1] // bl)

    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bc, bl), lambda i, j, k: (i, k)),
            pl.BlockSpec((1, bl), lambda i, j, k: (0, k)),
            pl.BlockSpec((bl, bd), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bc, bd), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((g.shape[0], x.shape[1]), jnp.float32),
        interpret=interpret,
    )(g, w[None, :], x)
    return out[:c, :d].astype(x.dtype)


# ---------------------------------------------------------------------------
# In-kernel PRNG variant: counter-based threefry generator tiles
# ---------------------------------------------------------------------------

_THREEFRY_PARITY = np.uint32(0x1BD11BDA)


def _rotl(x: jax.Array, r: int) -> jax.Array:
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def _threefry2x32(k0, k1, x0, x1):
    """The threefry-2x32 hash on uint32 arrays — identical rounds, key
    schedule, and constants to `jax._src.prng.threefry2x32` (unrolled)."""
    rot_a = (13, 15, 26, 6)
    rot_b = (17, 29, 16, 24)
    ks = (k0, k1, k0 ^ k1 ^ _THREEFRY_PARITY)

    def four_rounds(x0, x1, rots):
        for r in rots:
            x0 = x0 + x1
            x1 = _rotl(x1, r)
            x1 = x0 ^ x1
        return x0, x1

    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for i, rots in enumerate((rot_a, rot_b, rot_a, rot_b, rot_a)):
        x0, x1 = four_rounds(x0, x1, rots)
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + jnp.uint32(i + 1)
    return x0, x1


def _threefry_bits_at(k0, k1, idx: jax.Array) -> jax.Array:
    """`jax.random.bits(key, shape)`'s uint32 stream at flat positions
    `idx` — the partitionable layout: each value hashes its own 64-bit
    counter pair (hi, lo) = (0, idx) and XORs the two output words, so a
    tile of the stream costs one hash per element and depends on nothing
    but its flat position (streams stay below 2**32 values)."""
    out0, out1 = _threefry2x32(jnp.uint32(k0), jnp.uint32(k1),
                               jnp.zeros(idx.shape, jnp.uint32),
                               idx.astype(jnp.uint32))
    return out0 ^ out1


def _bits_to_generator(bits: jax.Array, kind: str) -> jax.Array:
    """uint32 bits -> generator entries, replaying `jax.random`'s exact
    bits-to-float path (mantissa fill in [1, 2), shift to the target
    interval) so entries match the host generator bit-for-bit."""
    one_bits = jnp.uint32(np.float32(1.0).view(np.uint32))
    float_bits = (bits >> jnp.uint32(9)) | one_bits
    floats = jax.lax.bitcast_convert_type(float_bits, jnp.float32) \
        - jnp.float32(1.0)
    if kind == "normal":
        lo = np.nextafter(np.float32(-1.0), np.float32(0.0),
                          dtype=np.float32)
        u = jnp.maximum(jnp.float32(lo),
                        floats * (jnp.float32(1.0) - jnp.float32(lo))
                        + jnp.float32(lo))
        return jnp.asarray(np.float32(np.sqrt(2))) * jax.lax.erf_inv(u)
    if kind == "bernoulli":  # rademacher: +-1 from a fair bernoulli draw
        u = jnp.maximum(jnp.float32(0.0), floats)
        return jnp.where(u < jnp.float32(0.5), jnp.float32(1.0),
                         jnp.float32(-1.0))
    raise ValueError(f"unknown generator kind: {kind}")


def generator_values(key: jax.Array, c: int, ell: int,
                     kind: str = "normal") -> jax.Array:
    """Host oracle: the full (c, ell) generator the in-kernel tiles
    produce — bit-identical to `core.encoding.generator_matrix(key, ...)`
    (enforced in tests/test_kernels.py)."""
    idx = jnp.arange(c * ell, dtype=jnp.int32).reshape(c, ell)
    bits = _threefry_bits_at(key[0], key[1], idx)
    return _bits_to_generator(bits, kind)


def _make_prng_kernel(ell: int, kind: str, block):
    bc, _, bl = block

    def kernel(key_ref, w_ref, x_ref, out_ref):
        i = pl.program_id(0)
        k = pl.program_id(2)

        @pl.when(k == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        # global (row, col) ids of this generator tile; padded cols fold
        # into later rows' flat indices, but their weights are zero-padded
        # so the spurious entries contribute exactly 0 (padded rows are
        # sliced off the output)
        rows = i * bc + jax.lax.broadcasted_iota(jnp.int32, (bc, bl), 0)
        cols = k * bl + jax.lax.broadcasted_iota(jnp.int32, (bc, bl), 1)
        bits = _threefry_bits_at(key_ref[0, 0], key_ref[0, 1],
                                 rows * ell + cols)
        g = _bits_to_generator(bits, kind)

        w = w_ref[...]                            # (1, bl)
        x = x_ref[...]                            # (bl, bd)
        xw = x * w[0][:, None].astype(x.dtype)    # fused diagonal scaling
        out_ref[...] += jax.lax.dot(g, xw, precision=_F32,
                                    preferred_element_type=jnp.float32
                                    ).astype(out_ref.dtype)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("c", "kind", "block", "interpret"))
def encode_parity_prng(key: jax.Array, w: jax.Array, x: jax.Array, c: int,
                       kind: str = "normal",
                       block: tuple[int, int, int] = DEFAULT_BLOCK,
                       interpret: bool = False) -> jax.Array:
    """P = G @ (diag(w) X) with G generated INSIDE the kernel.

    key: (2,) uint32 legacy PRNG key (one client's fold of the fleet key)
    w: (L,), x: (L, D) -> (C, D)

    The (c, ell) generator block is never materialized — each grid step
    regenerates its (bc, bl) tile from the key in VMEM/registers.  Entries
    are bit-identical to `generator_matrix(key, c, ell, kind)`.
    """
    ell, d = x.shape
    assert w.shape == (ell,)
    bc, bd, bl = block
    bc, bd, bl = min(bc, c), min(bd, d), min(bl, ell)
    pd, pL = (-d) % bd, (-ell) % bl
    if pL or pd:
        x = jnp.pad(x, ((0, pL), (0, pd)))
    if pL:
        w = jnp.pad(w, (0, pL))
    c_pad = c + ((-c) % bc)
    grid = (c_pad // bc, x.shape[1] // bd, x.shape[0] // bl)

    out = pl.pallas_call(
        _make_prng_kernel(ell, kind, (bc, bd, bl)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 2), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, bl), lambda i, j, k: (0, k)),
            pl.BlockSpec((bl, bd), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bc, bd), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((c_pad, x.shape[1]), jnp.float32),
        interpret=interpret,
    )(key.reshape(1, 2).astype(jnp.uint32), w[None, :], x)
    return out[:c, :d].astype(x.dtype)
