"""`Session` and the batched sweep engine.

One `Session` replaces the three copy-pasted Python epoch loops that used to
live in `sim.simulator.run_uncoded` / `run_cfl`, `fed.trainer`, and the
gradient-coding script: the strategy pre-samples every epoch's
delays/arrivals up front on the host (NumPy, shape `(epochs, n)`), and the
whole training trace — gradient estimate, GD update, NMSE — executes in one
jitted `jax.lax.scan`.  The device is synced exactly once per run (to fetch
the final NMSE trace) instead of once per epoch, which is what dominated
wall time at small `d`.

Since the sweep-engine refactor the scan body lives in a PURE BATCHED CORE:
a solo `Session.run` is a size-1 batch of the same compiled computation
that `run_sweep` uses to execute a whole sweep of sessions at once.  Lanes
(sessions) are grouped into shape buckets — same strategy static structure,
same operand shapes — and each bucket compiles ONE engine: a
`jax.lax.map` over the per-device lanes inside a `shard_map` over the lane
mesh (`repro.launch.mesh.make_lane_mesh`).  Every lane therefore executes
the exact same unbatched per-lane program whether it runs alone or in a
64-lane sweep, which is what makes the per-lane traces bit-for-bit equal
to solo runs (`tests/test_run_sweep.py`) — a `vmap` over lanes would not
be: XLA:CPU's batched/gemm lowerings change last-ulp results with the
batch size.

Lifecycle:

    data    = TrainData.linreg(jax.random.PRNGKey(0), n=24, ell=300, d=500)
    fleet   = paper_fleet(0.2, 0.2, seed=0)
    session = Session(strategy=CodedFL(key=jax.random.PRNGKey(1),
                                       fixed_c=2016),
                      fleet=fleet, lr=0.0085, epochs=600)
    report  = session.run(data)          # -> TraceReport

    # a whole sweep: one batched planning solve + one compiled engine
    # per shape bucket, sharded over the device mesh
    reports = run_sweep([session_a, session_b, ...], data)

Compiled engines are cached at MODULE level, keyed by the strategy's full
static structure (every primitive dataclass field that could steer the
trace, not just `engine_key`) plus the operand shapes and the lane count —
so sweeps, re-runs, and sessions cloned via `dataclasses.replace` share
compiled engines exactly when their traced computation is identical, and
never otherwise.
"""
from __future__ import annotations

import dataclasses
import os
from collections import OrderedDict
from typing import (TYPE_CHECKING, Any, Callable, Dict, Hashable, List,
                    Optional, Sequence)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import aggregation

from .report import TraceReport
from .strategy import EpochSchedule, Strategy, TrainData

if TYPE_CHECKING:  # annotation-only: keeps the api layer free of sim imports
    from repro.sim.network import FleetSpec

# Compiled sweep engines, shared by every Session in the process: one entry
# per (strategy static structure, operand shapes, lane count).  A 16-lane
# delta sweep compiles once per shape bucket instead of once per Session,
# and solo re-runs of equivalent sessions never retrace.  Each engine's
# closure pins its bucket's first strategy state (which can hold MB-scale
# parity arrays), so the cache is a BOUNDED LRU: least-recently-used
# entries evict once the cap is exceeded (fleet-scale bucketing — many
# topologies × shape buckets — would otherwise grow it for process
# lifetime).  Cap defaults to _ENGINE_CACHE_MAX; override per process
# with REPRO_ENGINE_CACHE_MAX.  All lookups go through `cache_engine`,
# shared with the serving engine (`repro.serving.fed_engine`).
_ENGINE_CACHE: "OrderedDict[Hashable, Callable]" = OrderedDict()
_ENGINE_CACHE_MAX = 64


def engine_cache_max() -> int:
    """Effective LRU capacity (env override, floor 1)."""
    try:
        return max(1, int(os.environ["REPRO_ENGINE_CACHE_MAX"]))
    except (KeyError, ValueError):
        return _ENGINE_CACHE_MAX


def cache_engine(key: Hashable, build: Callable[[], Callable]) -> Callable:
    """Fetch (or build) a compiled engine through the shared LRU.

    A hit refreshes the key's recency; a miss builds, inserts, and evicts
    least-recently-used entries past the cap.  Evicted engines keep
    working for holders of a direct reference (the serving engine's lane
    groups pin their own `step_fn`; sessions mirror engines in
    `_engines`), so eviction never breaks an in-flight bucket — it only
    forces the next cold lookup to recompile.
    """
    engine = _ENGINE_CACHE.get(key)
    if engine is not None:
        _ENGINE_CACHE.move_to_end(key)
        return engine
    engine = build()
    _ENGINE_CACHE[key] = engine
    cap = engine_cache_max()
    while len(_ENGINE_CACHE) > cap:
        _ENGINE_CACHE.popitem(last=False)
    return engine

_PRIMITIVES = (bool, int, float, str, bytes, type(None))


def _static_strategy_key(strategy: Strategy) -> Hashable:
    """Full static identity of a strategy's traced computation.

    Includes the class (module-qualified) and every primitive-valued
    dataclass field, EXCEPT `label` (display-only by protocol) and the
    fields the strategy declares in `engine_value_fields` — knobs that
    only change operand VALUES (plan inputs, host-side sampling, report
    metadata), never the traced engine.  Array-valued fields (PRNG keys,
    pre-solved plans) only ever feed operand values and are skipped.

    Keying on everything static by default means a strategy whose
    `engine_key` under-reports (the historical failure mode: clone a
    session via `dataclasses.replace` with a changed static field and
    silently reuse the old compiled engine) still never shares a compiled
    engine across trace-relevant differences.
    """
    cls = type(strategy)
    parts: List[Any] = [f"{cls.__module__}.{cls.__qualname__}"]
    skip = set(getattr(strategy, "engine_value_fields", ())) | {"label"}
    if dataclasses.is_dataclass(strategy):
        fields = [f.name for f in dataclasses.fields(strategy)]
    else:  # non-dataclass user strategies: their primitive attributes
        fields = sorted(k for k in getattr(strategy, "__dict__", {}))
    for name in fields:
        if name in skip:
            continue
        value = getattr(strategy, name)
        if isinstance(value, _PRIMITIVES):
            parts.append((name, type(value).__name__, value))
    return tuple(parts)


def _tree_shape_key(tree: Dict[str, Any]) -> Hashable:
    return tuple(sorted((k, tuple(v.shape), str(v.dtype))
                        for k, v in tree.items()))


def _bucket_key(strategy: Strategy, state: Any, data: TrainData,
                dev: Dict[str, jax.Array],
                arrivals: Dict[str, np.ndarray]) -> Hashable:
    """Sessions with equal keys run as lanes of one compiled engine."""
    return (_static_strategy_key(strategy),
            strategy.engine_key(state),
            data.m, data.d, data.model_dim, str(data.xs.dtype),
            _tree_shape_key(dev), _tree_shape_key(arrivals))


def make_epoch_step(strategy: Strategy, state: Any, m: int) -> Callable:
    """Build THE per-epoch training program for one strategy state.

    Returns `step(beta, dev, lr, beta_true, arr_t) -> (beta', nmse')`:
    one gradient round (`round_contributions`), one GD update (Eq. 3),
    one NMSE probe — exactly the body of the classic epoch loop.

    Every engine closes over this one function: the sweep engine's
    `lax.scan` body below (solo `Session.run` included, as a size-1
    sweep) and the serving engine's `lax.while_loop` body
    (`repro.serving.fed_engine`).  Sharing the program — not hoping two
    copies stay in sync — is what makes a served lane's trace
    bit-for-bit prefix-equal to the same session's fixed-epoch solo run.
    """
    m_s = jnp.asarray(m, dtype=jnp.int32)

    def step(beta: jax.Array, dev: Dict[str, jax.Array], lr: jax.Array,
             beta_true: jax.Array,
             arr_t: Dict[str, jax.Array]) -> tuple:
        g = strategy.round_contributions(state, dev, beta, arr_t)
        beta = aggregation.gd_update(beta, g, lr, m_s)
        return beta, aggregation.nmse(beta, beta_true)

    return step


def _build_engine(strategy: Strategy, state: Any, data: TrainData,
                  shared: Dict[str, jax.Array], args: tuple) -> Callable:
    """Compile the batched engine for one shape bucket.

    `shared` holds the lane-invariant device operands (the strategy's
    declared `data_device_keys` plus `beta_true`), replicated across the
    mesh instead of stacked B times — the training matrices are the bulk
    of the operand bytes and every lane reads the same ones.  `args` =
    (dev_lanes, arrivals, lr), every leaf stacked on a leading lane axis
    of size B.  The per-lane program is the classic solo scan engine;
    lanes are split over the lane mesh by `shard_map` and iterated per
    device with `jax.lax.map`, so each lane's arithmetic is identical at
    every B (the bit-for-bit guarantee — see module docstring).
    """
    from repro.launch.mesh import make_lane_mesh
    from repro.launch.sharding import lane_specs

    d, dtype = data.model_dim, data.xs.dtype
    n_lanes = jax.tree.leaves(args)[0].shape[0]
    mesh = make_lane_mesh(n_lanes)
    epoch_step = make_epoch_step(strategy, state, data.m)

    def lanes(shared_op, *lane_args):
        beta_true = shared_op.pop("beta_true")

        def lane(op):
            dev_lane, arr, lr = op
            dev = {**shared_op, **dev_lane}
            # lr rides in as a per-lane scalar operand: identical
            # arithmetic to the legacy closed-over constant
            beta0 = jnp.zeros(d, dtype=dtype)

            def step(beta, arr_t):
                return epoch_step(beta, dev, lr, beta_true, arr_t)

            # the carry varies over `lanes` once a step has run, so the
            # initial carry must too
            beta_f, trace = jax.lax.scan(
                step, jax.lax.pcast(beta0, "lanes", to="varying"), arr)
            nmse0 = aggregation.nmse(beta0, beta_true)
            return jnp.concatenate([nmse0[None], trace]), beta_f

        return jax.lax.map(lane, lane_args)

    replicated = jax.tree.map(lambda leaf: P(), shared)
    fn = jax.shard_map(lanes, mesh=mesh,
                       in_specs=(replicated,) + tuple(
                           lane_specs(a) for a in args),
                       out_specs=(P("lanes"), P("lanes")))
    # compiled ahead of the call for this bucket's exact shapes, so the
    # engine's program text (`as_text()`) can be inspected
    return jax.jit(fn).lower(shared, *args).compile()


def _execute_lanes(entries: Sequence[tuple],
                   data: TrainData) -> List[tuple]:
    """Run every (session, state, schedule) lane through the batched core.

    Lanes are grouped into shape buckets; each bucket stacks its operands,
    fetches (or compiles) its engine from the module cache and executes
    all its lanes in one sharded call.  Returns each lane's
    ((epochs+1,) NMSE trace, (model_dim,) final beta), in order.
    """
    devs: List[Dict[str, jax.Array]] = []
    arrs: List[Dict[str, np.ndarray]] = []
    buckets: Dict[Hashable, List[int]] = {}
    for i, (sess, state, sched) in enumerate(entries):
        dev = sess.strategy.device_state(state, data)
        arr = {k: np.asarray(v) for k, v in sched.arrivals.items()}
        devs.append(dev)
        arrs.append(arr)
        key = _bucket_key(sess.strategy, state, data, dev, arr)
        buckets.setdefault(key, []).append(i)

    dtype = data.xs.dtype
    results: List[Optional[tuple]] = [None] * len(entries)
    for key, idxs in buckets.items():
        b = len(idxs)
        sess0, state0, _ = entries[idxs[0]]
        # operands the strategy declares as pure functions of `data` are
        # lane-invariant within one call: pass ONE copy, replicated, and
        # stack only the genuinely per-lane state
        data_keys = set(getattr(sess0.strategy, "data_device_keys", ())) \
            & set(devs[idxs[0]])
        shared = {k: devs[idxs[0]][k] for k in data_keys}
        shared["beta_true"] = data.beta_true
        dev_b = {k: jnp.stack([devs[i][k] for i in idxs])
                 for k in devs[idxs[0]] if k not in data_keys}
        arr_b = {k: jnp.asarray(np.stack([arrs[i][k] for i in idxs]))
                 for k in arrs[idxs[0]]}
        lr_b = jnp.asarray(np.asarray([entries[i][0].lr for i in idxs]),
                           dtype=dtype)
        args = (dev_b, arr_b, lr_b)

        engine_key = (key, b)
        engine = cache_engine(
            engine_key,
            lambda: _build_engine(sess0.strategy, state0, data, shared,
                                  args))
        out_trace, out_beta = engine(shared, *args)
        out_trace, out_beta = np.asarray(out_trace), np.asarray(out_beta)
        for j, i in enumerate(idxs):
            results[i] = (out_trace[j], out_beta[j])
            # per-session mirror: introspection + lifetime of the session
            entries[i][0]._engines[engine_key] = engine
    return results  # type: ignore[return-value]


def _lane_report(session: "Session", state: Any, sched: EpochSchedule,
                 nmse_trace: np.ndarray,
                 label: Optional[str] = None,
                 beta: Optional[np.ndarray] = None) -> TraceReport:
    """Assemble the TraceReport for one lane — ONE code path for solo runs
    and sweep lanes, so their reports cannot drift."""
    times = sched.t0 + np.concatenate([[0.0], np.cumsum(sched.durations)])
    extras_fn = getattr(session.strategy, "report_extras", None)
    return TraceReport(
        times=times,
        nmse=nmse_trace,
        epoch_durations=np.asarray(sched.durations),
        label=label if label is not None else session.strategy.label,
        setup_time=sched.setup_time,
        uplink_bits_total=session.strategy.uplink_bits(
            state, session.fleet, session.epochs),
        extras=dict(extras_fn(state)) if extras_fn is not None else {},
        beta=beta)


@dataclasses.dataclass
class Session:
    """Runs one strategy over one fleet with a scan-jitted epoch engine.

    strategy: the coding scheme (UncodedFL / CodedFL / GradientCodingFL /
              any user Strategy)
    fleet:    delay + link parameters of the simulated fleet
    lr:       GD step size (Eq. 3)
    epochs:   number of training epochs per run
    seed:     default NumPy seed for delay sampling when `run` is not handed
              an explicit generator
    """

    strategy: Strategy
    fleet: "FleetSpec"
    lr: float
    epochs: int
    seed: int = 0

    def __post_init__(self):
        if self.epochs < 0:
            raise ValueError(f"epochs must be >= 0, got {self.epochs}")
        # local view into the shared module-level engine cache (see
        # _execute_lanes); compiled engines outlive any one session
        self._engines: Dict[Hashable, Callable] = {}

    # -- public API --------------------------------------------------------

    def plan(self, data: TrainData):
        """Run the strategy's one-time setup (exposed so sweeps and
        benchmarks can amortize planning/encoding across runs)."""
        return self.strategy.plan(self.fleet, data)

    def run(self, data: TrainData,
            rng: Optional[np.random.Generator] = None,
            label: Optional[str] = None, state=None) -> TraceReport:
        """Plan (unless a pre-planned `state` is given), pre-sample, and
        execute the full training trace — a size-1 batch of the shared
        sweep engine."""
        if rng is None:
            rng = np.random.default_rng(self.seed)
        if state is None:
            state = self.strategy.plan(self.fleet, data)
        sched: EpochSchedule = self.strategy.sample_epochs(
            state, self.fleet, self.epochs, rng)
        nmse_trace, beta = _execute_lanes([(self, state, sched)], data)[0]
        return _lane_report(self, state, sched, nmse_trace, label, beta=beta)


def plan_sweep(sessions: Sequence[Session], data: TrainData) -> List[Any]:
    """Plan every session's strategy, solving all redundancy problems in ONE
    batched call.

    Strategies exposing the batched-planning hooks (`plan_request(fleet,
    data) -> repro.plan.PlanRequest` and `plan_with(fleet, data, plan) ->
    state`, e.g. `CodedFL`) have their Eq. 14-16 solves collected into a
    single `repro.plan.solve_redundancy_batched` invocation — a 16-point
    delta sweep pays for one vectorized solve instead of 16 scalar ones.
    Everything else (and strategies carrying a pre-solved
    `redundancy_plan`) falls back to its own `plan`.

    Returns one strategy state per session, in order; pass each to
    `Session.run(data, state=...)` or all of them to
    `run_sweep(..., states=...)`.
    """
    states: List[Any] = [None] * len(sessions)
    batched: List[int] = []
    requests = []
    for i, sess in enumerate(sessions):
        strat = sess.strategy
        if hasattr(strat, "plan_request") and hasattr(strat, "plan_with") \
                and getattr(strat, "redundancy_plan", None) is None:
            requests.append(strat.plan_request(sess.fleet, data))
            batched.append(i)
    if requests:
        from repro.plan import solve_redundancy_batched
        plans = solve_redundancy_batched(requests)
        for i, plan in zip(batched, plans):
            states[i] = sessions[i].strategy.plan_with(
                sessions[i].fleet, data, plan)
    for i, sess in enumerate(sessions):
        if states[i] is None:
            states[i] = sess.plan(data)
    return states


def run_sweep(sessions: Sequence[Session], data: TrainData,
              rngs: Optional[Sequence[np.random.Generator]] = None,
              states: Optional[Sequence[Any]] = None) -> List[TraceReport]:
    """Execute a whole sweep of sessions as one batched computation.

    The three phases, each batched:

      1. planning — `plan_sweep` collects every session's allocation solve
         into one `repro.plan.solve_redundancy_batched` call (skipped for
         pre-planned `states`);
      2. sampling — each lane pre-samples its own epoch randomness on the
         host via the strategy's `sweep_inputs` hook (falling back to
         `sample_epochs`), with a PER-LANE generator so the draw order is
         identical to a solo `Session.run`;
      3. training — lanes are grouped into shape buckets (strategy static
         structure + operand shapes) and each bucket runs as ONE compiled
         engine, sharded over the lane mesh.

    Per-lane results — NMSE trace, wall-clock times, `TraceReport.extras`
    — are bit-for-bit identical to running each session solo with the
    same generator.

    rngs:   one generator per session (default: a fresh
            `np.random.default_rng(session.seed)` each, matching the solo
            `run` default)
    states: pre-planned strategy states (e.g. from `plan_sweep`, to time
            or amortize planning separately)
    """
    sessions = list(sessions)
    if states is None:
        states = plan_sweep(sessions, data)
    elif len(states) != len(sessions):
        raise ValueError(
            f"got {len(states)} states for {len(sessions)} sessions")
    if rngs is None:
        rngs = [np.random.default_rng(sess.seed) for sess in sessions]
    elif len(rngs) != len(sessions):
        raise ValueError(
            f"got {len(rngs)} generators for {len(sessions)} sessions")

    entries = []
    for sess, state, rng in zip(sessions, states, rngs):
        sample = getattr(sess.strategy, "sweep_inputs",
                         sess.strategy.sample_epochs)
        entries.append((sess, state,
                        sample(state, sess.fleet, sess.epochs, rng)))
    results = _execute_lanes(entries, data)
    return [_lane_report(sess, state, sched, trace, beta=beta)
            for (sess, state, sched), (trace, beta) in zip(entries, results)]
