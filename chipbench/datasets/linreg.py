"""§IV linear regression (arXiv:2002.09574): every client holds `ell` rows
of X iid N(0, 1), y = X beta + z, made on the device from the seed."""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from deploy import derive


@functools.partial(jax.jit, static_argnames=("n", "ell", "d"))
def _linreg(key, noise_std, *, n: int, ell: int, d: int):
    """§IV data: X iid N(0, 1), beta ~ N(0, 1)^d, y = X beta + z."""
    k1, k2, k3 = jax.random.split(key, 3)
    xs = jax.random.normal(k1, (n, ell, d), jnp.float32)
    beta = jax.random.normal(k2, (d,), jnp.float32)
    z = noise_std * jax.random.normal(k3, (n, ell), jnp.float32)
    ys = jnp.einsum("nld,d->nl", xs, beta,
                    precision=jax.lax.Precision.HIGHEST) + z
    return xs, ys, beta


def build(spec: Dict[str, Any], seed: int):
    """(TrainData, rows per client)."""
    from repro.api import TrainData

    key = jax.random.PRNGKey(derive(seed, 0))
    xs, ys, beta = _linreg(key, jnp.float32(spec["noise_std"]),
                           n=spec["n"], ell=spec["ell"], d=spec["d"])
    return (TrainData(xs=xs, ys=ys, beta_true=beta),
            np.full(spec["n"], spec["ell"]))
