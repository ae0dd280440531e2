"""Roofline-pruned tile search: enumerate, prune, measure, persist.

For one `(family, shape)` the tuner:

  1. enumerates the family's candidate tile grid (backend-aware);
  2. DRY-RUN lowers each candidate — `jax.jit(fn).lower(sds).compile()`
     over ShapeDtypeStructs, no arrays allocated — and feeds the HLO
     text to `roofline.analysis.roofline_terms`.  The bound
     `max(t_compute, t_memory)` is a lower limit on achievable time
     under the roofline model: a candidate whose bound exceeds
     `slack x` the best bound cannot win unless the model is off by
     more than `slack`, so it is pruned WITHOUT execution.  (Tile
     choice moves the memory term a lot — small tiles re-stream the
     resident operands once per grid step — while FLOPs stay constant,
     so the bound separates candidates sharply.);
  3. measures the survivors (median-free mean of `iters` timed calls
     after a warmup) and picks the winner deterministically: ties break
     toward the earlier candidate in enumeration order;
  4. persists the winner in the on-disk tile cache keyed by
     `(family, shape bucket, backend)` — `block="auto"` then serves it
     process-wide with zero measurement cost.

Autotuning is always EXPLICIT (this module or `python -m repro.tune`);
`block="auto"` only ever reads the cache.

`terms_fn` / `measure_fn` are injectable for tests (deterministic
winner selection and pruning proofs without compiling kernels).
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Callable, Optional

import jax

from repro.kernels import common as kcommon
from repro.roofline.analysis import roofline_terms

from .cache import CACHE_ENV, TileCache, bucket_shape, user_cache_path
from .families import FAMILIES

# How far a candidate's roofline lower bound may sit above the best
# candidate's before it is pruned unmeasured.  The slack absorbs the
# model's attainment gap (a kept candidate may run `slack x` above its
# bound and still beat a pruned one at its bound).
DEFAULT_SLACK = float(os.environ.get("REPRO_TUNE_PRUNE_SLACK", "8.0"))


@dataclasses.dataclass(frozen=True)
class TuneResult:
    family: str
    shape: tuple
    bucket: tuple
    backend: str
    block: tuple            # the winner
    us: float               # its measured time
    bound_us: float         # its roofline lower bound
    candidates: tuple       # full enumeration order
    bounds_us: tuple        # lower bound per candidate (same order)
    pruned: tuple           # candidates skipped by the roofline model
    measured: tuple         # (block, us) per survivor

    def meta(self, extra: Optional[dict] = None) -> dict:
        m = {"us": round(self.us, 1), "bound_us": round(self.bound_us, 3),
             "n_candidates": len(self.candidates),
             "n_pruned": len(self.pruned),
             "shape": list(self.shape), "jax": jax.__version__,
             "source": "measured"}
        m.update(extra or {})
        return m


def roofline_bound(terms: dict) -> float:
    """Achievable-time lower limit: the binding compute/memory term."""
    return max(terms["t_compute"], terms["t_memory"])


def candidate_terms(family, shape, block) -> dict:
    """Roofline terms from a dry-run lowering of one candidate (no
    arrays are materialized; interpret-mode lowerings off-TPU still
    carry the grid/tile structure, so bytes scale with grid steps)."""
    fn, sds = family.bind(shape, block)
    hlo = jax.jit(fn).lower(*sds).compile().as_text()
    return roofline_terms(hlo, 1)


def measure(fn, args, iters: int = 5) -> float:
    """Mean wall time (us) of `iters` calls after one warmup call."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def prune(candidates: list, bounds_us: list,
          slack: float = DEFAULT_SLACK) -> tuple[list, list]:
    """(survivors, pruned): keep candidates within `slack x` of the best
    roofline bound.  Every pruned candidate is dominated UNDER THE
    MODEL: its lower bound alone exceeds what the best candidate could
    take even running `slack x` above its own bound."""
    best = min(bounds_us)
    if best <= 0:
        # Degenerate lowering (zero roofline bound): the slack band would
        # collapse to 0 and prune every positive-bound candidate, so the
        # model can't rank anything — measure them all instead.
        return list(candidates), []
    survivors = [c for c, b in zip(candidates, bounds_us)
                 if b <= slack * best]
    pruned = [c for c, b in zip(candidates, bounds_us)
              if b > slack * best]
    return survivors, pruned


def autotune(family_name: str, shape: tuple, *,
             slack: float = DEFAULT_SLACK, iters: int = 5,
             backend: Optional[str] = None,
             cache: Optional[TileCache] = None, store: bool = True,
             terms_fn: Optional[Callable] = None,
             measure_fn: Optional[Callable] = None,
             verbose: bool = False) -> TuneResult:
    """Tune one `(family, shape)` and (by default) persist the winner."""
    family = FAMILIES[family_name]
    backend = backend or kcommon.backend()
    candidates = family.candidate_blocks(shape, backend)
    if terms_fn is None:
        def terms_fn(block):
            return candidate_terms(family, shape, block)
    bounds = [roofline_bound(terms_fn(b)) * 1e6 for b in candidates]
    survivors, pruned = prune(candidates, bounds, slack=slack)

    if measure_fn is None:
        def measure_fn(block):
            fn, _ = family.bind(shape, block)
            return measure(jax.jit(fn), family.make_args(shape),
                           iters=iters)
    timed = [(measure_fn(b), i, b) for i, b in enumerate(survivors)]
    best_us, _, winner = min(timed)  # ties -> earliest candidate

    result = TuneResult(
        family=family_name, shape=tuple(shape),
        bucket=bucket_shape(shape), backend=backend,
        block=tuple(winner), us=float(best_us),
        bound_us=float(bounds[candidates.index(winner)]),
        candidates=tuple(candidates), bounds_us=tuple(bounds),
        pruned=tuple(pruned),
        measured=tuple((b, float(us)) for us, _, b in timed))
    if verbose:
        print(f"tune {family_name} {shape} [{backend}]: "
              f"{len(candidates)} candidates, {len(pruned)} pruned, "
              f"winner {winner} at {best_us:.0f}us")
    if store:
        if cache is None:
            path = user_cache_path()
            if path is None:
                raise ValueError("no tile cache to store into: set "
                                 f"${CACHE_ENV} or pass cache=")
            cache = TileCache(path)
        cache.store(family_name, shape, backend, winner, result.meta())
    return result


def tune_shapes(shapes: Optional[dict] = None, *,
                cache: Optional[TileCache] = None,
                slack: float = DEFAULT_SLACK, iters: int = 5,
                verbose: bool = True) -> list[TuneResult]:
    """Tune a `{family: [shape, ...]}` map (defaults to the CI set)."""
    from .families import CI_SHAPES

    shapes = shapes if shapes is not None else CI_SHAPES
    results = []
    for family_name, shape_list in shapes.items():
        for shape in shape_list:
            results.append(autotune(family_name, tuple(shape),
                                    slack=slack, iters=iters,
                                    cache=cache, verbose=verbose))
    return results
