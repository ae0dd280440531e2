"""Client-side random linear encoding of training data (paper §III-A, Eqs. 9-12).

Each client i draws a private generator matrix G_i in R^{c x ell_i} with iid
N(0,1) entries (Bernoulli(1/2) +-1 also supported) and a diagonal weight
matrix W_i (Eq. 17), then uploads only

    X~_i = G_i W_i X_i,      y~_i = G_i W_i y_i.

The server sums the client parities into the composite parity dataset
(X~, y~) = (sum_i X~_i, sum_i y~_i) = (G W X, G W y) — a distributed encoding
of the full decentralized dataset in which G, W, X, y all stay unknown to the
server.  Puncturing (w=1 rows that the client never processes locally) is
implicit in the weight vector.

Encoding is a batched matmul; the Pallas path in `repro.kernels.encode`
fuses generator sampling + diagonal scaling + matmul accumulation end-to-end,
streamed one client at a time.  This module is the pure-JAX reference path
used by default on CPU; its fleet encoder streams clients through a
`lax.scan` accumulation so the (n, c, d) parity stack never materializes.

The `use_kernel` branches call the kernel ops at their `block="auto"`
default, so tiles come from the persisted autotuner cache
(`repro.tune`) — every consumer (CFL setup, the scheme strategies, the
sweep and serving engines) inherits tuned tiles with zero plumbing.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro import obs


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ClientParity:
    """Parity shards produced by one client."""

    x_parity: jax.Array  # (c, d)
    y_parity: jax.Array  # (c,)


def generator_matrix(key: jax.Array, c: int, ell: int,
                     kind: str = "normal", dtype=jnp.float32) -> jax.Array:
    """Random generator matrix G in R^{c x ell}."""
    if kind == "normal":
        return jax.random.normal(key, (c, ell), dtype=dtype)
    if kind == "bernoulli":
        # +-1 with prob 1/2 each: E[G^T G]/c = I still holds.
        return jax.random.rademacher(key, (c, ell), dtype=dtype)
    raise ValueError(f"unknown generator kind: {kind}")


@partial(jax.jit, static_argnames=("use_kernel",))
def encode_client(g: jax.Array, w: jax.Array, x: jax.Array, y: jax.Array,
                  use_kernel: bool = False) -> ClientParity:
    """(X~, y~) = (G W X, G W y) for one client.

    g: (c, ell)   private generator matrix
    w: (ell,)     diagonal of the weight matrix (Eq. 17)
    x: (ell, d)   local features
    y: (ell,)     local labels
    """
    if use_kernel:
        from repro.kernels.encode import ops as encode_ops
        xp = encode_ops.encode_parity(g, w, x)
    else:
        xp = g @ (w[:, None] * x)
    yp = g @ (w * y)
    return ClientParity(x_parity=xp, y_parity=yp)


def encode_fleet_streamed(keys: jax.Array, xs: jax.Array, ys: jax.Array,
                          weights: jax.Array, c: int, kind: str,
                          client_encode) -> tuple[jax.Array, jax.Array]:
    """Shared streaming core behind both fleet encoders.

    Clients are streamed through a `lax.scan` accumulation: one (c, ell)
    generator and one (c, d+1) accumulator live at a time — never the
    (n, c, ell) generator stack or the (n, c, d) parity stack (peak memory
    matters for large-c sweeps).  The labels ride along as an extra feature
    column so X~ and y~ come out of one fused `client_encode(g, w, x)` call
    per client (pure matmul here, Pallas kernel in `repro.kernels.encode`).
    """
    n, ell, d = xs.shape
    xa = jnp.concatenate([xs, ys[..., None]], axis=-1)  # (n, ell, d+1)

    def one(acc, inp):
        k, x, w = inp
        g = generator_matrix(k, c, ell, kind=kind, dtype=xs.dtype)
        return acc + client_encode(g, w, x), None

    acc, _ = jax.lax.scan(one, jnp.zeros((c, d + 1), dtype=xs.dtype),
                          (keys, xa, weights))
    return acc[:, :d], acc[:, d]


def encode_fleet(key: jax.Array, xs: jax.Array, ys: jax.Array,
                 weights: jax.Array, c: int, kind: str = "normal",
                 use_kernel: bool = False) -> tuple[jax.Array, jax.Array]:
    """Encode every client and return the composite parity dataset.

    xs: (n, ell, d) stacked client features (equal-size shards)
    ys: (n, ell)    stacked client labels
    weights: (n, ell) per-client weight diagonals
    Returns (X~ (c, d), y~ (c,)) = sums of per-client parities.

    Each client uses an independent fold of `key` — mirroring the protocol
    where G_i is drawn locally and never shared; both paths stream through
    `encode_fleet_streamed` and therefore draw identical generators.  The
    host span `repro.encode` covers the dispatch; the encode itself runs
    asynchronously on the device.
    """
    with obs.span("repro.encode", c=int(c)):
        return _encode_fleet(key, xs, ys, weights, c, kind=kind,
                             use_kernel=use_kernel)


@partial(jax.jit, static_argnames=("c", "kind", "use_kernel"))
def _encode_fleet(key: jax.Array, xs: jax.Array, ys: jax.Array,
                  weights: jax.Array, c: int, kind: str,
                  use_kernel: bool) -> tuple[jax.Array, jax.Array]:
    keys = jax.random.split(key, xs.shape[0])
    if use_kernel:
        from repro.kernels.encode import ops as encode_ops
        return encode_fleet_streamed(keys, xs, ys, weights, c, kind,
                                     encode_ops.encode_parity)
    return encode_fleet_streamed(keys, xs, ys, weights, c, kind,
                                 lambda g, w, x: g @ (w[:, None] * x))
