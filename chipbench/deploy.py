"""Build a configuration's deployment from its file and the run's seed.

    system = build(config_dict, seed)
    system.data, system.fleet        # what the program is given
    system.ref_fleet                 # the same fleet, built by the reference
    strategy(system, "cfl", key=k)   # one of the configuration's strategies

Inputs are made from `--seed` alone: the data on the device in one jitted
call, the fleet on the host, each from its own stream of the seed.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from reference import cfl as ref


def derive(seed: int, *tags: int) -> int:
    """A 31-bit integer drawn from (seed, *tags); seeds may be any size."""
    ss = np.random.SeedSequence([int(seed) % 2 ** 64, *tags])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


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


def _data(spec: Dict[str, Any], seed: int):
    from repro.api import TrainData

    key = jax.random.PRNGKey(derive(seed, 0))
    if spec["kind"] == "linreg":
        xs, ys, beta = _linreg(key, jnp.float32(spec["noise_std"]),
                               n=spec["n"], ell=spec["ell"], d=spec["d"])
        return TrainData(xs=xs, ys=ys, beta_true=beta)
    if spec["kind"] == "classification_rff":
        xs, ys = _classification(
            key, jnp.float32(spec["teacher_gamma"]), n=spec["n"],
            ell=spec["ell"], d=spec["d"], n_classes=spec["n_classes"],
            centers=spec["centers"], target=spec["target_class"])
        head = spec["head"]
        # the kernel regressor the NMSE is measured against: the least-
        # squares head on the features, from its normal equations
        gram, rhs = _normal_equations(
            xs, ys, jax.random.PRNGKey(derive(seed, 7)),
            jnp.float32(head["rff_gamma"]), d_feat=head["d_feat"])
        beta = np.linalg.lstsq(np.asarray(gram, np.float64),
                               np.asarray(rhs, np.float64), rcond=None)[0]
        return TrainData(xs=xs, ys=ys,
                         beta_true=jnp.asarray(beta, jnp.float32))
    raise ValueError(f"unknown data kind {spec['kind']!r}")


def _fleets(spec: Dict[str, Any], data_spec: Dict[str, Any], seed: int):
    from repro.sim import network

    s = derive(seed, 1)
    n, d = data_spec["n"], spec.get("d", data_spec["d"])
    if spec["kind"] == "paper_fleet":
        prog = network.paper_fleet(spec["nu_comp"], spec["nu_link"],
                                   seed=s, n=n, d=d)
        return prog, ref.paper_fleet(n, d, spec["nu_comp"],
                                     spec["nu_link"], s)
    if spec["kind"] == "wireless_fleet":
        prog = network.wireless_fleet(spec["nu_comp"], spec["nu_link"],
                                      spec["nu_erasure"], seed=s, n=n, d=d)
        return prog, ref.wireless_fleet(n, d, spec["nu_comp"],
                                        spec["nu_link"], spec["nu_erasure"],
                                        s)
    raise ValueError(f"unknown fleet kind {spec['kind']!r}")


@dataclasses.dataclass
class System:
    cfg: Dict[str, Any]
    seed: int
    data: Any
    fleet: Any
    ref_fleet: ref.Fleet
    rff_key: int = 0
    # what the reference computes once per run (host data, features,
    # deadlines), kept for every answer it compares
    cache: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def sizes(self) -> np.ndarray:
        return np.full(self.cfg["data"]["n"], self.cfg["data"]["ell"])


def build(cfg: Dict[str, Any], seed: int) -> System:
    data = _data(cfg["data"], seed)
    jax.block_until_ready(data.xs)
    fleet, ref_fleet = _fleets(cfg["fleet"], cfg["data"], seed)
    return System(cfg=cfg, seed=seed, data=data, fleet=fleet,
                  ref_fleet=ref_fleet, rff_key=derive(seed, 7))


def strategy(system: System, name: str, key: int, **overrides):
    """The configuration's strategy `name`, keyed by `key` where it draws
    generator matrices; a strategy on the data's feature head shares the
    run's feature map (`rff_key`, one per run, so the head stays valid)."""
    from repro.api import make_strategy

    spec = dict(system.cfg["strategies"][name], **overrides)
    kind = spec.pop("kind")
    if spec.pop("keyed", False):
        spec["key"] = jax.random.PRNGKey(key)
    if spec.pop("head", False):
        head = system.cfg["data"]["head"]
        spec.update(d_feat=head["d_feat"], rff_gamma=head["rff_gamma"],
                    rff_key=jax.random.PRNGKey(system.rff_key))
    return make_strategy(kind, **spec)
