"""Classification at a dataset's shape for a random Fourier feature head
(CodedFedL, arXiv:2007.03273): inputs iid N(0, 1), +-1 one-vs-rest labels
from a random RBF-network teacher, every client holding `ell` rows; the
head the NMSE is measured against is the least-squares head on the
features.  Made on the device from the seed."""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from deploy import derive


@functools.partial(jax.jit, static_argnames=(
    "n", "ell", "d", "n_classes", "centers", "target"))
def _classification(key, teacher_gamma, *, n: int, ell: int, d: int,
                    n_classes: int, centers: int, target: int):
    """Inputs iid N(0, 1); labels the argmax of a random RBF-network
    teacher, sum_j A[c, j] exp(-gamma |x - z_j|^2 / d) over random
    centres z_j; one-vs-rest +-1 targets for class `target`."""
    hi = jax.lax.Precision.HIGHEST
    k1, k2, k3 = jax.random.split(key, 3)
    xs = jax.random.normal(k1, (n, ell, d), jnp.float32)
    zc = jax.random.normal(k2, (centers, d), jnp.float32)
    amp = jax.random.normal(k3, (n_classes, centers), jnp.float32)
    sq = (jnp.sum(xs ** 2, axis=-1, keepdims=True)
          - 2.0 * jnp.matmul(xs, zc.T, precision=hi)
          + jnp.sum(zc ** 2, axis=-1))
    score = jnp.matmul(jnp.exp(-teacher_gamma * sq / d), amp.T,
                       precision=hi)
    return xs, jnp.where(jnp.argmax(score, axis=-1) == target, 1.0, -1.0)


@functools.partial(jax.jit, static_argnames=("d_feat",))
def _normal_equations(xs, ys, key, gamma, *, d_feat: int):
    """(Phi^T Phi, Phi^T y) of the random Fourier features of xs."""
    hi = jax.lax.Precision.HIGHEST
    x = xs.reshape(-1, xs.shape[-1])
    w = jnp.sqrt(2.0 * gamma) * jax.random.normal(
        key, (x.shape[-1], d_feat // 2), jnp.float32)
    proj = jnp.matmul(x, w, precision=hi)
    phi = jnp.sqrt(2.0 / d_feat) * jnp.concatenate(
        [jnp.cos(proj), jnp.sin(proj)], axis=-1)
    return (jnp.matmul(phi.T, phi, precision=hi),
            jnp.matmul(ys.reshape(-1), phi, precision=hi))


def build(spec: Dict[str, Any], seed: int):
    """(TrainData, rows per client).  The feature map's key is
    `derive(seed, 7)`, the one the run's strategies are given."""
    from repro.api import TrainData

    key = jax.random.PRNGKey(derive(seed, 0))
    xs, ys = _classification(
        key, jnp.float32(spec["teacher_gamma"]), n=spec["n"],
        ell=spec["ell"], d=spec["d"], n_classes=spec["n_classes"],
        centers=spec["centers"], target=spec["target_class"])
    head = spec["head"]
    gram, rhs = _normal_equations(
        xs, ys, jax.random.PRNGKey(derive(seed, 7)),
        jnp.float32(head["rff_gamma"]), d_feat=head["d_feat"])
    beta = np.linalg.lstsq(np.asarray(gram, np.float64),
                           np.asarray(rhs, np.float64), rcond=None)[0]
    return (TrainData(xs=xs, ys=ys, beta_true=jnp.asarray(beta, jnp.float32)),
            np.full(spec["n"], spec["ell"]))
