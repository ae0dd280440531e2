"""CodedFedL non-linear benchmark: RFF kernel classification vs linear.

The CodedFedL scenario (arXiv:2007.03273): clients hold raw inputs whose
class boundaries are non-linear (`repro.data.classification_dataset`'s
RBF-network teacher), push them through the shared random-Fourier-feature
map, and CFL-train a least-squares one-vs-rest head in feature space
under the MEC delay model.  Three comparisons:

  * **coded vs uncoded at equal wall-clock** — the headline gate: the
    coded run's deadline-t* epochs buy more gradient steps per second
    than the uncoded straggler-wait, so at the coded run's finish time
    its test accuracy must be at least the uncoded head's.  The
    equal-time uncoded head comes from a re-run at the epoch count that
    fits in the coded wall-clock budget (prefix-identical draws, so it
    IS the full run's trajectory truncated).
  * **kernel vs best-linear** — the non-linearity gate: the GD-trained
    feature-space head must beat the closed-form least-squares head on
    the RAW inputs (the best any linear model could do), otherwise the
    kernel machinery isn't earning its keep.
  * **Pallas encode parity** — the feature-space parity encode through
    `encoding.encode_fleet(use_kernel=True)` (tuned `block="auto"`
    tiles) must match the XLA path on the same features, weights and
    key, so the accelerated encode composes with the new strategy.

    PYTHONPATH=src python -m benchmarks.fig_nonlinear [--epochs 600]
    PYTHONPATH=src python -m benchmarks.fig_nonlinear --smoke   # CI gate

`--smoke` runs one small configuration and writes the gate values
(`coded_accuracy`, `uncoded_accuracy_equal_time`, `linear_accuracy`) to
BENCH_nonlinear.json for the perf-trend trajectory.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import Session, TrainData, make_strategy
from repro.data import classification_dataset, one_vs_rest_targets
from repro.sim.network import wireless_fleet

from .common import Timer, dump_bench, emit

# §V-style configuration, scaled to CI: binary labels from a 32-centre
# RBF teacher in 6 raw dimensions, 256 Fourier features, 12 clients.
N_DEVICES = 12
ELL_TRAIN = 100
ELL_TEST = 50
D_RAW = 6
D_FEAT = 256
CENTERS = 32
TEACHER_GAMMA = 2.0
DATA_SEED = 2
LR = 0.5
DELTA = 0.3


def make_problem(seed: int = DATA_SEED):
    """Train/test split of one teacher's data + the strategy that maps it.

    Returns (data, strategy, phi_test (n*ell_te, D), y_test (n*ell_te,)).
    `data.beta_true` is the feature-space least-squares reference head, so
    the NMSE trace measures distance to the kernel regressor.
    """
    key = jax.random.PRNGKey(seed)
    xs, labels = classification_dataset(
        key, N_DEVICES, ELL_TRAIN + ELL_TEST, D_RAW,
        n_classes=2, centers=CENTERS, gamma=TEACHER_GAMMA)
    y = one_vs_rest_targets(labels, 1)
    xs_tr, xs_te = xs[:, :ELL_TRAIN], xs[:, ELL_TRAIN:]
    y_tr, y_te = y[:, :ELL_TRAIN], y[:, ELL_TRAIN:]

    strategy = make_strategy(
        "codedfedl", key_seed=7, d_feat=D_FEAT,
        rff_gamma=TEACHER_GAMMA / D_RAW,
        fixed_c=int(DELTA * N_DEVICES * ELL_TRAIN))
    dummy = TrainData(xs=xs_tr, ys=y_tr, beta_true=jnp.zeros(D_FEAT))
    phi_tr = np.asarray(strategy.features(dummy),
                        np.float64).reshape(-1, D_FEAT)
    beta_ref, *_ = np.linalg.lstsq(
        phi_tr, np.asarray(y_tr, np.float64).reshape(-1), rcond=None)
    data = TrainData(xs=xs_tr, ys=y_tr,
                     beta_true=jnp.asarray(beta_ref, jnp.float32))
    phi_te = np.asarray(
        strategy.features(TrainData(xs=xs_te, ys=y_te,
                                    beta_true=jnp.zeros(D_FEAT))),
        np.float64).reshape(-1, D_FEAT)
    return data, strategy, phi_te, np.asarray(y_te, np.float64).reshape(-1)


def sign_accuracy(phi: np.ndarray, beta: np.ndarray,
                  y: np.ndarray) -> float:
    return float(np.mean((phi @ np.asarray(beta, np.float64) > 0)
                         == (y > 0)))


def best_linear_accuracy(data: TrainData, phi_te_y: tuple) -> float:
    """Closed-form least-squares head on the RAW inputs — the ceiling for
    any linear model, trained or not (affine: a bias column is added)."""
    phi_te, y_te = phi_te_y
    del phi_te  # the linear head never sees the feature space
    key = jax.random.PRNGKey(DATA_SEED)
    xs, labels = classification_dataset(
        key, N_DEVICES, ELL_TRAIN + ELL_TEST, D_RAW,
        n_classes=2, centers=CENTERS, gamma=TEACHER_GAMMA)
    y = np.asarray(one_vs_rest_targets(labels, 1), np.float64)
    X = np.asarray(xs, np.float64)
    Xtr = X[:, :ELL_TRAIN].reshape(-1, D_RAW)
    Xte = X[:, ELL_TRAIN:].reshape(-1, D_RAW)
    ytr = y[:, :ELL_TRAIN].reshape(-1)
    b, *_ = np.linalg.lstsq(np.c_[Xtr, np.ones(len(Xtr))], ytr, rcond=None)
    pred = np.c_[Xte, np.ones(len(Xte))] @ b
    return float(np.mean((pred > 0) == (y_te > 0)))


def equal_time_epochs(uncoded_rep, t_budget: float) -> int:
    """Largest epoch count whose cumulative uncoded wall-clock fits in
    `t_budget` (the coded run's finish time)."""
    cum = np.cumsum(uncoded_rep.epoch_durations)
    return int(np.searchsorted(cum, t_budget, side="right"))


def run_pair(fleet, data, strategy, epochs: int, seed: int = 0):
    """Coded run + full uncoded run + uncoded re-run at equal wall-clock.

    The uncoded arm trains the SAME feature-space objective (pre-mapped
    inputs), so the only difference is the epoch protocol."""
    coded = Session(strategy=strategy, fleet=fleet, lr=LR,
                    epochs=epochs).run(data,
                                       rng=np.random.default_rng(seed))
    feat_data = TrainData(xs=strategy.features(data), ys=data.ys,
                          beta_true=data.beta_true)
    base = Session(strategy=make_strategy("uncoded"), fleet=fleet, lr=LR,
                   epochs=epochs)
    uncoded = base.run(feat_data, rng=np.random.default_rng(seed))
    e_eq = equal_time_epochs(uncoded, coded.times[-1])
    # prefix-identical draws: the truncated run IS the full trajectory
    # at epoch e_eq, harvested through the engine's final-beta slot
    eq = Session(strategy=make_strategy("uncoded"), fleet=fleet, lr=LR,
                 epochs=e_eq).run(feat_data,
                                  rng=np.random.default_rng(seed))
    assert np.array_equal(np.asarray(eq.nmse),
                          np.asarray(uncoded.nmse[:e_eq + 1])), \
        "equal-time uncoded re-run diverged from the full trajectory"
    return coded, uncoded, eq


def encode_kernel_parity(fleet, data, strategy) -> float:
    """Max |Pallas - XLA| over the feature-space parity encode."""
    from repro.core import encoding
    from repro.core.redundancy import systematic_weights
    state = strategy.plan(fleet, data)
    weights = jnp.asarray(np.stack(systematic_weights(
        state.plan, np.full(data.n, data.ell))), dtype=data.xs.dtype)
    x_par = {}
    for use_kernel in (False, True):
        x_par[use_kernel], _ = encoding.encode_fleet(
            strategy.key, state.features, data.ys, weights, state.c,
            kind=strategy.generator, use_kernel=use_kernel)
    return float(jnp.max(jnp.abs(x_par[True] - x_par[False])))


# ---------------------------------------------------------------------------
# smoke mode (CI)
# ---------------------------------------------------------------------------

def smoke(epochs: int = 300) -> None:
    fleet = wireless_fleet(0.3, 0.3, nu_erasure=0.3, seed=0,
                           n=N_DEVICES, d=D_FEAT)
    data, strategy, phi_te, y_te = make_problem()

    with Timer() as t:
        coded, uncoded, eq = run_pair(fleet, data, strategy, epochs)
    acc_coded = sign_accuracy(phi_te, coded.beta, y_te)
    acc_eq = sign_accuracy(phi_te, eq.beta, y_te)
    acc_lin = best_linear_accuracy(data, (phi_te, y_te))
    enc_err = encode_kernel_parity(fleet, data, strategy)

    emit("fig_nonlinear/smoke_pair", t.us / (3 * epochs),
         f"coded_acc={acc_coded:.4f};eq_time_acc={acc_eq:.4f};"
         f"eq_epochs={eq.epochs};t_coded={coded.times[-1]:.0f}s")
    emit("fig_nonlinear/encode_kernel_parity", 0.0,
         f"max_abs_err={enc_err:.3e}")
    gates = {"coded_accuracy": round(acc_coded, 4),
             "uncoded_accuracy_equal_time": round(acc_eq, 4),
             "linear_accuracy": round(acc_lin, 4),
             "equal_time_epochs": eq.epochs,
             "coded_final_nmse": coded.final_nmse(),
             "encode_kernel_max_err": enc_err}
    try:
        assert np.all(np.isfinite(coded.nmse)), "coded trace has NaNs"
        assert coded.final_nmse() < coded.nmse[0], \
            "coded kernel head does not descend"
        assert acc_coded >= acc_eq, \
            f"coded head ({acc_coded:.4f}) lost to the uncoded head at " \
            f"equal wall-clock ({acc_eq:.4f})"
        assert acc_coded > acc_lin + 0.02, \
            f"kernel head ({acc_coded:.4f}) does not beat the best " \
            f"linear model ({acc_lin:.4f}) — feature map is not earning"
        assert enc_err < 1e-3, \
            f"Pallas feature-encode diverged from XLA by {enc_err:.3e}"
    finally:
        dump_bench("nonlinear", gates=gates)
    print("fig_nonlinear --smoke OK (coded >= equal-time uncoded, "
          "kernel > linear, encode parity)")


# ---------------------------------------------------------------------------
# full mode
# ---------------------------------------------------------------------------

def main(epochs: int = 600) -> None:
    fleet = wireless_fleet(0.3, 0.3, nu_erasure=0.3, seed=0,
                           n=N_DEVICES, d=D_FEAT)
    data, strategy, phi_te, y_te = make_problem()

    with Timer() as t:
        coded, uncoded, eq = run_pair(fleet, data, strategy, epochs)
    acc_coded = sign_accuracy(phi_te, coded.beta, y_te)
    acc_full = sign_accuracy(phi_te, uncoded.beta, y_te)
    acc_eq = sign_accuracy(phi_te, eq.beta, y_te)
    acc_lin = best_linear_accuracy(data, (phi_te, y_te))
    emit("fig_nonlinear/head_to_head", t.us / (3 * epochs),
         f"coded_acc={acc_coded:.4f};uncoded_full={acc_full:.4f};"
         f"uncoded_equal_time={acc_eq:.4f};linear={acc_lin:.4f};"
         f"eq_epochs={eq.epochs};t_coded={coded.times[-1]:.0f}s;"
         f"t_uncoded={uncoded.times[-1]:.0f}s")
    assert acc_coded >= acc_eq
    assert acc_coded > acc_lin

    # accuracy vs feature width: more Fourier features approximate the
    # teacher kernel better (monotone up to estimation noise)
    import dataclasses
    for d_feat in (32, 128, 512):
        strat = dataclasses.replace(strategy, d_feat=d_feat)
        dummy = TrainData(xs=data.xs, ys=data.ys,
                          beta_true=jnp.zeros(d_feat))
        phi = np.asarray(strat.features(dummy),
                         np.float64).reshape(-1, d_feat)
        beta_ref, *_ = np.linalg.lstsq(
            phi, np.asarray(data.ys, np.float64).reshape(-1), rcond=None)
        dd = TrainData(xs=data.xs, ys=data.ys,
                       beta_true=jnp.asarray(beta_ref, jnp.float32))
        rep = Session(strategy=strat, fleet=fleet, lr=LR,
                      epochs=epochs).run(dd, rng=np.random.default_rng(0))
        xs_te_raw = classification_dataset(
            jax.random.PRNGKey(DATA_SEED), N_DEVICES,
            ELL_TRAIN + ELL_TEST, D_RAW, n_classes=2, centers=CENTERS,
            gamma=TEACHER_GAMMA)[0][:, ELL_TRAIN:]
        pte = np.asarray(
            strat.features(TrainData(xs=xs_te_raw, ys=jnp.zeros(
                xs_te_raw.shape[:2]), beta_true=jnp.zeros(d_feat))),
            np.float64).reshape(-1, d_feat)
        acc = sign_accuracy(pte, rep.beta, y_te)
        emit(f"fig_nonlinear/width_{d_feat}", 0.0,
             f"accuracy={acc:.4f};final_nmse={rep.final_nmse():.3f};"
             f"t_star={rep.epoch_durations[0]:.2f}s")
        assert np.all(np.isfinite(rep.nmse))


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=600)
    ap.add_argument("--smoke", action="store_true",
                    help="quick CI mode: one configuration, assert gates")
    args = ap.parse_args()
    if args.smoke:
        smoke()
    else:
        main(epochs=args.epochs)
