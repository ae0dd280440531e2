"""Decide `correct`: what the timed path returned against the reference.

An answer is one session's result as the timed path produced it: its
plan, its parity block, its NMSE trace, final beta and clock.  `compare`
computes the same session with the float64 reference and gives one
number per comparison; `verdict` holds each worst number to its limit
from the configuration file.

    nums = compare(system, answer)           # {"nmse_rel": ..., ...}
    checks, ok = verdict(system.cfg, [nums, ...])

The reference is the module the configuration names (`"reference":
"<name>"`, `chipbench/reference/<name>.py`, `system.reference`), which
imports nothing of `src/` and exposes

    answer(system, name, key, rng, overrides, ar, t_star=None) -> Answer
    root(system, spec)   the least deadline t_root of a coded session's
                         plan, or None for an uncoded one
    work(system, name, rng, plan) -> count.Work of the session's epochs,
                         from the arrival masks `answer` draws for it

The numbers:
  t_star_gap    (t*_program - t_root) / t_root: Eq. 16 holds at t* and
                t* lies within the configuration's plan_eps_rel of the
                least deadline t_root (0 <= gap <= limit)
  loads_diff    clients whose load differs from the reference's best load
                at the program's t* (exact: limit 0)
  p_return_gap  largest |Pr{T_i <= t*}| difference at those loads
  parity_rel    largest |[X~, Y~] - reference| over the largest reference
                entry
  clock_diff    largest |time| difference of the snapshots' clock (exact)
  nmse_rel      largest relative difference of the NMSE over all epochs
  beta_rel      ||beta - beta_ref|| / ||beta_ref|| of the final model
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from reference import FLOAT64, Arith


@dataclasses.dataclass
class Answer:
    """One session as the timed path (or a stand-in for it) returned it."""

    name: str                  # the configuration's strategy name
    key: int                   # the strategy's generator key
    rng: int                   # seed of the session's delay generator
    overrides: Dict[str, Any]  # strategy fields set by the traffic
    nmse: np.ndarray
    beta: np.ndarray
    times: np.ndarray
    t_star: Optional[float] = None
    loads: Optional[np.ndarray] = None
    p_return: Optional[np.ndarray] = None
    parity: Optional[np.ndarray] = None  # (c, d + K): [X~, Y~]


def program_answer(name, key, rng, overrides, state, report) -> Answer:
    """Copy what the program returned to the host."""
    ans = Answer(name, key, rng, dict(overrides), np.asarray(report.nmse),
                 np.asarray(report.beta), np.asarray(report.times))
    plan = getattr(state, "plan", None)
    if plan is not None and getattr(state, "x_parity", None) is not None:
        ans.t_star = float(plan.t_star)
        ans.loads = np.asarray(plan.loads)
        ans.p_return = np.asarray(plan.p_return)
        x_par = np.asarray(state.x_parity, np.float64)
        ans.parity = np.concatenate(
            [x_par, np.asarray(state.y_parity,
                               np.float64).reshape(x_par.shape[0], -1)],
            axis=1)
    return ans


def _host_data(system):
    if "host" not in system.cache:
        d = system.data
        system.cache["host"] = (np.asarray(d.xs), np.asarray(d.ys),
                                np.asarray(d.beta_true))
    return system.cache["host"]


def reference_answer(system, name: str, key: int, rng: int,
                     overrides: Dict[str, Any], ar: Arith,
                     t_star: Optional[float] = None) -> Answer:
    """The session computed by the configuration's reference module in
    the precision `ar`.  A coded session takes its deadline from `t_star`
    where given (the program's, which `t_star_gap` judges), else solves
    for it itself."""
    return system.reference.answer(system, name, key, rng, overrides, ar,
                                   t_star)


def _rel_max(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a) - b)) / np.max(np.abs(b)))


def compare(system, ans: Answer) -> Dict[str, float]:
    """The numbers of one answer against the float64 reference."""
    ref_ans = reference_answer(system, ans.name, ans.key, ans.rng,
                               ans.overrides, FLOAT64, t_star=ans.t_star)
    out: Dict[str, float] = {}
    if ans.t_star is not None:
        root = system.reference.root(system,
                                     system.spec(ans.name, ans.overrides))
        out["t_star_gap"] = (ans.t_star - root) / root
        out["loads_diff"] = float(np.sum(ans.loads != ref_ans.loads))
        out["p_return_gap"] = float(np.max(np.abs(
            ans.p_return - ref_ans.p_return)))
        out["parity_rel"] = _rel_max(ans.parity, ref_ans.parity)
    out["clock_diff"] = float(np.max(np.abs(ans.times - ref_ans.times)))
    out["nmse_rel"] = float(np.max(np.abs(ans.nmse - ref_ans.nmse)
                                   / ref_ans.nmse))
    beta, ref_beta = np.ravel(ans.beta), np.ravel(ref_ans.beta)
    out["beta_rel"] = float(np.linalg.norm(beta - ref_beta)
                            / np.linalg.norm(ref_beta))
    return out


# numbers that must also not fall below 0
TWO_SIDED = ("t_star_gap",)


def verdict(cfg: Dict[str, Any], numbers: List[Dict[str, float]]
            ) -> Tuple[Dict[str, Dict[str, float]], bool]:
    """Worst value of each number over the answers, beside its limit.  No
    answer at all is not correct.  Only the numbers the configuration
    gives a limit are held to one."""
    limits = cfg["limits"]
    worst: Dict[str, float] = {}
    for nums in numbers:
        for k, v in nums.items():
            if k not in limits:
                continue
            if k in TWO_SIDED:
                # keep the value farthest outside [0, limit]
                if k not in worst or abs(v - limits[k] / 2) > abs(
                        worst[k] - limits[k] / 2):
                    worst[k] = v
            elif not (v <= worst.get(k, -np.inf)):
                worst[k] = v
    checks: Dict[str, Dict[str, float]] = {}
    ok = len(numbers) >= 1
    for k, v in worst.items():
        lim = limits[k]
        checks[k] = {"value": v, "limit": lim}
        good = bool(np.isfinite(v)) and v <= lim
        if k in TWO_SIDED:
            good = good and v >= 0.0
        ok = ok and good
    return checks, ok
