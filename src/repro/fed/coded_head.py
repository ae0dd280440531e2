"""Exact CFL for a model's linear readout head.

The paper's parity-gradient identity holds whenever the trained parameters
enter linearly under squared loss.  For a deep network with a frozen
backbone this is exactly the last-layer (linear-probe) setting: client i
holds features Phi_i = f_theta(X_i) in R^{ell_i x d_feat} and regression
targets y_i; training the head beta solves min ||Phi beta - y||^2 — the
paper's problem verbatim, with Phi in place of X.

Two feature sources compose here:

  * an explicit frozen backbone from `repro.models` (`backbone_fn`), or
  * `CodedFedL`'s random-Fourier-feature map (`d_feat=...`), which turns
    the head into Gaussian-kernel regression on the raw inputs
    (arXiv:2007.03273) — no backbone weights needed.

Runs ride the Strategy/Session substrate (`UncodedFL` baseline,
`CodedFL` / `CodedFedL` coded head) and return `TraceReport`s, so the
full coded machinery — batched redundancy solve, private parity upload,
deadline-clipped epochs — trains the head with the paper's guarantees.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import numpy as np

from repro.api import Session, TrainData
from repro.api.report import TraceReport
from repro.api.strategy import CodedFL, UncodedFL
from repro.schemes import CodedFedL
from repro.sim.network import FleetSpec


def extract_features(backbone_fn: Callable, xs: jax.Array) -> jax.Array:
    """Apply a frozen backbone per client. xs (n, ell, ...) -> (n, ell, d)."""
    return jax.vmap(backbone_fn)(xs)


def train_coded_head(fleet: FleetSpec, backbone_fn: Optional[Callable],
                     xs: jax.Array, ys: jax.Array, beta_true: jax.Array,
                     lr: float, epochs: int, key: jax.Array,
                     rng: np.random.Generator,
                     fixed_c: Optional[int] = None,
                     include_upload_delay: bool = False,
                     uncoded_baseline: bool = True,
                     d_feat: Optional[int] = None,
                     rff_key: Optional[jax.Array] = None,
                     rff_gamma: float = 1.0
                     ) -> dict[str, TraceReport]:
    """Coded-train a linear head on (frozen-backbone or RFF) features.

    backbone_fn: maps one client's raw inputs (ell, ...) to features
    (ell, d_feat); None means features == inputs (pure linreg).
    d_feat/rff_key/rff_gamma: push the (backbone) features through
    `CodedFedL`'s shared RFF map and train the head in kernel space;
    `beta_true` is then replaced by the feature-space least-squares
    reference head, so the NMSE trace measures distance to the kernel
    regressor.
    Returns {"uncoded": TraceReport, "cfl" | "cfedl": TraceReport};
    the shared `rng` is consumed sequentially (uncoded first).
    """
    feats = extract_features(backbone_fn, xs) if backbone_fn is not None \
        else xs

    if d_feat is None:
        coded_key = "cfl"
        coded = CodedFL(key=key, fixed_c=fixed_c,
                        include_upload_delay=include_upload_delay)
        data = TrainData(xs=feats, ys=ys, beta_true=beta_true)
    else:
        coded_key = "cfedl"
        coded = CodedFedL(key=key, d_feat=d_feat, rff_key=rff_key,
                          rff_gamma=rff_gamma, fixed_c=fixed_c,
                          include_upload_delay=include_upload_delay)
        # feature-space reference head: the model trains in d_feat
        # dimensions, so NMSE must be measured against the kernel
        # regressor, not the raw-space beta_true
        phi = np.asarray(coded.features(
            TrainData(xs=feats, ys=ys, beta_true=beta_true)))
        beta_ref, *_ = np.linalg.lstsq(
            phi.reshape(-1, d_feat),
            np.asarray(ys, dtype=np.float64).reshape(-1), rcond=None)
        data = TrainData(xs=feats, ys=ys,
                         beta_true=jax.numpy.asarray(
                             beta_ref, dtype=feats.dtype))

    out: dict[str, TraceReport] = {}
    if uncoded_baseline:
        # the uncoded baseline waits for every straggler on the SAME
        # training problem: kernel-space runs pre-map the features so
        # both arms descend the same objective
        base_xs = data.xs if d_feat is None else coded.features(data)
        base = TrainData(xs=base_xs, ys=data.ys, beta_true=data.beta_true)
        out["uncoded"] = Session(strategy=UncodedFL(), fleet=fleet,
                                 lr=lr, epochs=epochs).run(base, rng=rng)
    out[coded_key] = Session(strategy=coded, fleet=fleet,
                             lr=lr, epochs=epochs).run(data, rng=rng)
    return out
