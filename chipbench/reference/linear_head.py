"""The reference of a federated session on a linear head: the §IV linear
model (arXiv:2002.09574) and CodedFedL's random Fourier feature head
(arXiv:2007.03273), one output column.

Named by a configuration's `"reference": "linear_head"`; like every
reference module it exposes `answer`, `root` and `work` (`checks.py`).
Strategy kinds: "uncoded" (synchronous FL), "cfl" and "codedfedl" (coded,
under the delay model `MODEL` names).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

import checks
import count
from reference import FLOAT64, Arith
from reference import cfl as ref

# the delay model of each coded strategy kind
MODEL = {"cfl": "base", "codedfedl": "mec"}


def _features(system, ar: Arith) -> np.ndarray:
    """The data's random Fourier features, computed by the reference
    (kept per precision: every answer of a run shares them)."""
    import jax

    cache = system.cache.setdefault("features", {})
    if ar.name not in cache:
        head = system.cfg["data"]["head"]
        cache[ar.name] = ref.rff(checks._host_data(system)[0],
                                 jax.random.PRNGKey(system.rff_key),
                                 head["d_feat"], head["rff_gamma"], ar)
    return cache[ar.name]


def _width(system, spec: Dict[str, Any]) -> int:
    """The width of the rows the session trains on."""
    data = system.cfg["data"]
    return data["head"]["d_feat"] if spec.get("head") else data["d"]


def schedule(system, spec: Dict[str, Any], plan: Optional[ref.Plan],
             rng: int) -> ref.Schedule:
    """The session's arrival masks and clock, drawn by the reference
    sampler from its delay generator's seed `rng` (`plan` None where the
    strategy is uncoded)."""
    cfg = system.cfg
    gen = np.random.default_rng(rng)
    if spec["kind"] == "uncoded":
        return ref.sample_uncoded(system.ref_fleet, cfg["data"]["ell"],
                                  cfg["epochs"], gen)
    return ref.sample_coded(system.ref_fleet, plan, _width(system, spec),
                            cfg["epochs"], gen, MODEL[spec["kind"]])


def answer(system, name: str, key: int, rng: int,
           overrides: Dict[str, Any], ar: Arith,
           t_star: Optional[float] = None) -> checks.Answer:
    """The session computed in the precision `ar`.  A coded session takes
    its deadline from `t_star` where given (the program's, which
    `t_star_gap` judges), else solves Eq. 16 itself."""
    import jax

    cfg = system.cfg
    spec = system.spec(name, overrides)
    xs, ys, bt = checks._host_data(system)
    n, ell, d = xs.shape
    lr = cfg["lr"]
    x, y = xs.reshape(n * ell, d), ys.reshape(n * ell)
    row_client = np.repeat(np.arange(n), ell)
    ans = checks.Answer(name, key, rng, dict(overrides), None, None, None)
    if spec["kind"] == "uncoded":
        sched = schedule(system, spec, None, rng)
        nmse, beta = ref.train(ar, x, y, bt, lr, np.ones(n * ell),
                               row_client, sched.received)
    else:
        model = MODEL[spec["kind"]]
        if spec.get("head"):
            xs = _features(system, ar)
            d = xs.shape[-1]
            x = xs.reshape(n * ell, d)
        c = int(spec["fixed_c"])
        if t_star is None:
            t_star = ref.deadline(system.ref_fleet, system.sizes, c, ar,
                                  model)
        plan = ref.plan_at(system.ref_fleet, system.sizes, c, t_star, ar,
                           model)
        w = ref.weights(plan, ell)
        xp, yp = ref.encode(jax.random.PRNGKey(key), xs, ys, w, c, ar)
        sched = schedule(system, spec, plan, rng)
        rows = (np.arange(ell)[None, :] < plan.loads[:, None]).reshape(-1)
        nmse, beta = ref.train(ar, x, y, bt, lr, rows.astype(np.float64),
                               row_client, sched.received, (xp, yp),
                               sched.parity_ok)
        ans.t_star, ans.loads, ans.p_return = t_star, plan.loads, \
            plan.p_return
        ans.parity = np.concatenate([xp, yp[:, None]], 1).astype(np.float64)
    ans.nmse, ans.beta, ans.times = nmse, beta, sched.times
    return ans


def root(system, spec: Dict[str, Any]) -> Optional[float]:
    """The least deadline at which a coded session's plan meets Eq. 16
    (float64, once per run and parity budget); None for an uncoded one."""
    if spec["kind"] not in MODEL:
        return None
    c = int(spec["fixed_c"])
    roots = system.cache.setdefault("roots", {})
    if (spec["kind"], c) not in roots:
        roots[spec["kind"], c] = ref.deadline(
            system.ref_fleet, system.sizes, c, FLOAT64, MODEL[spec["kind"]])
    return roots[spec["kind"], c]


def work(system, name: str, rng: int, plan: Any) -> count.Work:
    """The count of the session's epochs, over the arrival masks `answer`
    draws for it: the program's `plan` (loads, c, t*) where it is coded."""
    spec = system.spec(name, {})
    width = _width(system, spec)
    if spec["kind"] == "uncoded":
        sched = schedule(system, spec, None, rng)
        return count.epoch_work(
            width, count.masked_rows(system.sizes, sched.received),
            outputs=1)
    p = ref.Plan(np.asarray(plan.loads), int(plan.c), float(plan.t_star),
                 np.asarray(plan.p_return), float("nan"))
    sched = schedule(system, spec, p, rng)
    return count.epoch_work(width, count.masked_rows(p.loads, sched.received),
                            p.c, sched.parity_ok, outputs=1)
