"""Always-on federated serving engine: continuous session batching with
convergence-based early exit.

`run_sweep` executes a STATIC list of sessions; production traffic is
sessions *arriving and departing*.  `FedServeEngine` is the long-lived
counterpart: training jobs are submitted at arrival times on a virtual
clock, admitted into warm, shape-bucketed **lane slots**, trained in
chunks of a compiled `lax.while_loop`, and harvested the moment their
convergence predicate fires — a converged lane frees its slot for the
next pending job instead of padding to the max epoch count.

Architecture (everything reused from the sweep engine, not forked):

  * **Shape buckets.**  A lane group is keyed by the sweep engine's own
    `_bucket_key` — strategy static structure + `engine_key` + operand
    shapes — so the jobs that would share one `run_sweep` engine share
    one serve group.  Each group holds `lane_width` slots; compiled
    programs live in the process-wide `repro.api.session._ENGINE_CACHE`,
    so a second engine instance (or a restart of the same traffic) finds
    its programs warm.
  * **One epoch program.**  The while-loop body calls
    `repro.api.make_epoch_step` — the SAME function the `lax.scan`
    engine closes over — and lanes are iterated with `lax.map` inside a
    `shard_map` over the lane mesh (`launch.mesh.make_lane_mesh`,
    `launch.sharding.lane_specs`), the sweep engine's bit-for-bit
    construction.  A served lane therefore executes the identical
    unbatched per-epoch program as a solo `Session.run`, which is what
    makes its trace bit-for-bit PREFIX-equal to the solo trace up to the
    reported exit epoch (`tests/test_fed_serve.py`).
  * **Convergence-based early exit.**  The per-lane predicate
    (`ConvergenceCriterion`: NMSE target, relative plateau, epoch
    budget) is evaluated INSIDE the compiled while loop, so a lane stops
    consuming compute the epoch it converges — no host round-trip per
    epoch, one per `chunk` epochs.  Strategies tighten the criterion via
    the optional `serve_convergence` hook (epsilon-budget exhaustion for
    `StochasticCodedFL`).  The exit point lands on
    `TraceReport.extras["serve_exit_epoch"]` (+ `serve_converged`,
    `serve_uid`), and a truncated run's `epsilon_schedule` /
    `epsilon_spent` / `uplink_bits_total` are priced at the epochs
    actually served.
  * **Donated buffers.**  The chunk step donates the lane carry (model
    iterates, epoch counters, trace rows) and admission splices a new
    job's operands into a finished lane's slot through a donated
    `dynamic-update` program — steady-state serving updates device
    buffers in place instead of reallocating per step.  Operand stacks
    (`dev`/`arrivals`) are donated only by the splice, never by the
    chunk step, which reuses them read-only across chunks.

Entry points: `submit`/`submit_many` + `step`/`drain` for long-lived
use, `serve(sessions, arrivals=...)` for the admit-everything-and-drain
pattern (the CLI `python -m repro.launch.fedserve` and
`benchmarks/perf_serve.py` drive both).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Hashable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.api import Session, TraceReport, plan_sweep
from repro.api.session import _bucket_key, cache_engine, make_epoch_step
from repro.api.strategy import EpochSchedule
from repro.core import aggregation

from .scheduler import ConvergenceCriterion, FifoScheduler, ServeRequest

# Fetch-or-build goes through the sweep engine's shared LRU
# (`repro.api.session.cache_engine`); lane groups additionally pin their
# own `step_fn`/`splice` references, so an eviction under REPRO_ENGINE_
# CACHE_MAX pressure never breaks an in-flight serve bucket.
_cache_engine = cache_engine


@partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _splice(carry, dev_b, arr_b, ctrl, slot, lane_carry, lane_dev,
            lane_arr, lane_ctrl):
    """Swap one lane's operands + carry into slot `slot`, in place.

    `slot` is a traced scalar so every swap reuses one compiled program
    per group; all four stacked trees are donated, so steady-state
    admission never reallocates the group's device state.
    """

    def set_lane(full, one):
        return full.at[slot].set(jnp.asarray(one, dtype=full.dtype))

    return (jax.tree.map(set_lane, carry, lane_carry),
            jax.tree.map(set_lane, dev_b, lane_dev),
            jax.tree.map(set_lane, arr_b, lane_arr),
            jax.tree.map(set_lane, ctrl, lane_ctrl))


def _build_serve_engine(strategy, state, data, shared, carry, dev_b, arr_b,
                        ctrl, chunk: int):
    """Compile one lane group's chunked while-loop program.

    Signature: `(shared, carry, dev_b, arr_b, ctrl) -> carry`.  Each
    call advances every non-stopped lane by up to `chunk` epochs, exiting
    a lane early the epoch its convergence predicate fires.  The carry is
    donated (in-place update); operand stacks are read-only here.
    """
    from repro.launch.mesh import make_lane_mesh
    from repro.launch.sharding import lane_specs

    epoch_step = make_epoch_step(strategy, state, data.m)
    n_lanes = ctrl["lr"].shape[0]
    mesh = make_lane_mesh(n_lanes)

    def lanes(shared_op, carry_b, dev_bb, arr_bb, ctrl_b):
        beta_true = shared_op.pop("beta_true")

        def lane(args):
            (beta, t, prev, trace, stop, conv), dev_lane, arr, cl = args
            dev = {**shared_op, **dev_lane}
            lr, budget = cl["lr"], cl["budget"]
            t_hi = jnp.minimum(t + chunk, budget)

            def cond(c):
                return jnp.logical_not(c[4]) & (c[1] < t_hi)

            def body(c):
                beta_c, t_c, prev_c, trace_c, _, _ = c
                arr_t = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, t_c, 0, keepdims=False), arr)
                beta_n, nm = epoch_step(beta_c, dev, lr, beta_true, arr_t)
                t_n = t_c + 1
                trace_n = trace_c.at[t_n].set(nm)
                # the early-exit predicate, evaluated on-device: absolute
                # NMSE target OR one-epoch relative plateau (rel_delta < 0
                # disables the plateau clause), gated by min_epochs
                hit = (nm <= cl["nmse_target"]) \
                    | (jnp.abs(prev_c - nm) <= cl["rel_delta"] * prev_c)
                conv_n = (t_n >= cl["min_epochs"]) & hit
                stop_n = conv_n | (t_n >= budget)
                return beta_n, t_n, nm, trace_n, stop_n, conv_n

            return jax.lax.while_loop(
                cond, body, (beta, t, prev, trace, stop, conv))

        return jax.lax.map(lane, (carry_b, dev_bb, arr_bb, ctrl_b))

    replicated = jax.tree.map(lambda _: P(), shared)
    fn = jax.shard_map(lanes, mesh=mesh,
                       in_specs=(replicated, lane_specs(carry),
                                 lane_specs(dev_b), lane_specs(arr_b),
                                 lane_specs(ctrl)),
                       out_specs=lane_specs(carry))
    return jax.jit(fn, donate_argnums=(1,))


@dataclasses.dataclass
class _Prepared:
    """A submitted request with its host-side work done: planned state,
    pre-sampled epoch schedule, device/arrival operands, bucket key and
    resolved epoch budget."""

    request: ServeRequest
    state: Any
    sched: EpochSchedule
    dev: Dict[str, jax.Array]
    arr: Dict[str, np.ndarray]
    key: Hashable
    criterion: ConvergenceCriterion
    budget: int


class _LaneGroup:
    """One shape bucket's warm slots: stacked operands, lane carry, and
    the compiled chunk program (shared via the process-wide cache)."""

    def __init__(self, engine: "FedServeEngine", key: Hashable,
                 template: _Prepared):
        data = engine.data
        strategy = template.request.session.strategy
        b = engine.lane_width
        dtype = data.xs.dtype
        epochs = int(np.asarray(template.sched.durations).shape[0])

        data_keys = set(getattr(strategy, "data_device_keys", ())) \
            & set(template.dev)
        self.data_keys = data_keys
        self.shared = {k: template.dev[k] for k in data_keys}
        self.shared["beta_true"] = data.beta_true
        self.epochs = epochs
        self.key = key

        self.dev_b = {k: jnp.zeros((b,) + tuple(v.shape), v.dtype)
                      for k, v in template.dev.items() if k not in data_keys}
        self.arr_b = {k: jnp.zeros((b,) + np.asarray(v).shape,
                                   np.asarray(v).dtype)
                      for k, v in template.arr.items()}
        self.ctrl = {"lr": jnp.zeros(b, dtype),
                     "nmse_target": jnp.zeros(b, dtype),
                     "rel_delta": jnp.full(b, -1.0, dtype),
                     "min_epochs": jnp.ones(b, jnp.int32),
                     "budget": jnp.zeros(b, jnp.int32)}
        nmse0 = engine._nmse0
        self.carry = (jnp.zeros((b, data.model_dim), dtype),
                      jnp.zeros(b, jnp.int32),
                      jnp.full(b, nmse0, dtype),
                      jnp.zeros((b, epochs + 1), dtype),
                      jnp.ones(b, bool),       # placeholder lanes: stopped
                      jnp.zeros(b, bool))
        self.slots: List[Optional[_Prepared]] = [None] * b

        self.step_fn = _cache_engine(
            ("serve", key, b, engine.chunk),
            lambda: _build_serve_engine(
                strategy, template.state, data, self.shared, self.carry,
                self.dev_b, self.arr_b, self.ctrl, engine.chunk))

    # ------------------------------------------------------------------
    def free_slot(self) -> Optional[int]:
        for i, occ in enumerate(self.slots):
            if occ is None:
                return i
        return None

    @property
    def running(self) -> bool:
        return any(occ is not None for occ in self.slots)

    def admit(self, engine: "FedServeEngine", prep: _Prepared,
              slot: int) -> None:
        data = engine.data
        dtype = data.xs.dtype
        crit = prep.criterion
        nmse0 = engine._nmse0
        trace0 = jnp.zeros(self.epochs + 1, dtype).at[0].set(nmse0)
        lane_carry = (jnp.zeros(data.model_dim, dtype), jnp.int32(0),
                      jnp.asarray(nmse0, dtype), trace0,
                      jnp.asarray(False), jnp.asarray(False))
        lane_dev = {k: prep.dev[k] for k in self.dev_b}
        lane_arr = {k: jnp.asarray(np.asarray(prep.arr[k]))
                    for k in self.arr_b}
        rel = -1.0 if crit.rel_delta is None else float(crit.rel_delta)
        lane_ctrl = {"lr": jnp.asarray(prep.request.session.lr, dtype),
                     "nmse_target": jnp.asarray(crit.nmse_target, dtype),
                     "rel_delta": jnp.asarray(rel, dtype),
                     "min_epochs": jnp.int32(crit.min_epochs),
                     "budget": jnp.int32(prep.budget)}
        self.carry, self.dev_b, self.arr_b, self.ctrl = _splice(
            self.carry, self.dev_b, self.arr_b, self.ctrl,
            jnp.int32(slot), lane_carry, lane_dev, lane_arr, lane_ctrl)
        self.slots[slot] = prep

    def step(self) -> List[Tuple[int, _Prepared, np.ndarray, int, bool,
                                 np.ndarray]]:
        """Advance all lanes one chunk; return the finished ones as
        `(slot, prepared, trace_row, exit_epoch, converged, beta)`."""
        self.carry = self.step_fn(self.shared, self.carry, self.dev_b,
                                  self.arr_b, self.ctrl)
        stop = np.asarray(self.carry[4])
        finished = []
        for slot, occ in enumerate(self.slots):
            if occ is None or not stop[slot]:
                continue
            t_exit = int(np.asarray(self.carry[1][slot]))
            trace = np.asarray(self.carry[3][slot])
            conv = bool(np.asarray(self.carry[5][slot]))
            beta = np.asarray(self.carry[0][slot])
            finished.append((slot, occ, trace, t_exit, conv, beta))
            self.slots[slot] = None
        return finished


class FedServeEngine:
    """The always-on serving loop over a fixed `TrainData` problem.

    data:       the training problem every served session runs on
    lane_width: slots per shape bucket (one compiled program per
                (bucket, lane_width); the lane mesh splits the slots
                over local devices)
    chunk:      epochs advanced per compiled step — the harvest/admission
                granularity.  Convergence still exits a lane at the exact
                epoch the predicate fires (the while loop checks every
                epoch); `chunk` only bounds how long a freed slot waits
                to be noticed.
    criterion:  engine-default `ConvergenceCriterion` (per-request
                overrides via `ServeRequest.criterion`; strategies
                tighten it via `serve_convergence`)
    """

    def __init__(self, data, *, lane_width: int = 4, chunk: int = 25,
                 criterion: ConvergenceCriterion = ConvergenceCriterion(),
                 max_groups: Optional[int] = None):
        if lane_width < 1:
            raise ValueError(f"lane_width must be >= 1, got {lane_width}")
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self.data = data
        self.lane_width = lane_width
        self.chunk = chunk
        self.criterion = criterion
        self.max_groups = max_groups
        self.now = 0.0
        self._scheduler = FifoScheduler()
        self._groups: Dict[Hashable, _LaneGroup] = {}
        self._prepared: Dict[int, _Prepared] = {}
        self._done: Dict[int, TraceReport] = {}
        self._uids: List[int] = []
        self._next_uid = 0
        self.steps = 0
        # the t=0 probe, computed by the same jitted expression the
        # engines trace (bit-equal to the solo trace's first entry)
        self._nmse0 = jax.jit(aggregation.nmse)(
            jnp.zeros(data.model_dim, data.xs.dtype), data.beta_true)

    # -- submission --------------------------------------------------------

    def submit(self, session: Session, *, uid: Optional[int] = None,
               arrival: Optional[float] = None, state: Any = None,
               rng_seed: Optional[int] = None,
               criterion: Optional[ConvergenceCriterion] = None) -> int:
        """Queue one session; returns its uid.  Host-side preparation
        (planning, epoch pre-sampling, operand layout) happens here, so
        admission into a freed lane is a single donated splice."""
        return self.submit_many(
            [session], uids=None if uid is None else [uid],
            arrivals=None if arrival is None else [arrival],
            states=None if state is None else [state],
            rng_seeds=None if rng_seed is None else [rng_seed],
            criteria=None if criterion is None else [criterion])[0]

    def submit_many(self, sessions: Sequence[Session], *,
                    uids: Optional[Sequence[int]] = None,
                    arrivals: Optional[Sequence[float]] = None,
                    states: Optional[Sequence[Any]] = None,
                    rng_seeds: Optional[Sequence[int]] = None,
                    criteria: Optional[
                        Sequence[ConvergenceCriterion]] = None) -> List[int]:
        """Queue a batch of sessions.  Unplanned strategies are planned
        through ONE batched `plan_sweep` call (the admission-cost story
        of the sweep engine carries over unchanged)."""
        sessions = list(sessions)
        if states is None:
            states = plan_sweep(sessions, self.data)
        out_uids: List[int] = []
        for i, (sess, st) in enumerate(zip(sessions, states)):
            uid = self._next_uid if uids is None else int(uids[i])
            if uid in self._prepared or uid in self._done:
                raise ValueError(f"duplicate serve uid {uid}")
            self._next_uid = max(self._next_uid, uid) + 1
            req = ServeRequest(
                session=sess, uid=uid,
                arrival=self.now if arrivals is None else float(arrivals[i]),
                rng_seed=None if rng_seeds is None else rng_seeds[i],
                state=st,
                criterion=None if criteria is None else criteria[i])
            prep = self._prepare(req)
            self._prepared[uid] = prep
            self._uids.append(uid)
            self._scheduler.push(req, prep.key)
            out_uids.append(uid)
        return out_uids

    def _prepare(self, req: ServeRequest) -> _Prepared:
        """Plan-independent host work for one request: pre-sample the
        epoch randomness with the request's IDENTITY-keyed generator
        (never a shared engine stream — see scheduler module docstring),
        lay out operands, resolve the bucket key and epoch budget."""
        sess = req.session
        state = req.state
        if state is None:
            state = sess.strategy.plan(sess.fleet, self.data)
        sample = getattr(sess.strategy, "sweep_inputs",
                         sess.strategy.sample_epochs)
        sched = sample(state, sess.fleet, sess.epochs, req.make_rng())
        dev = sess.strategy.device_state(state, self.data)
        arr = {k: np.asarray(v) for k, v in sched.arrivals.items()}
        key = _bucket_key(sess.strategy, state, self.data, dev, arr)
        crit = req.criterion if req.criterion is not None else self.criterion
        hook = getattr(sess.strategy, "serve_convergence", None)
        if hook is not None:
            crit = hook(state, crit)
        return _Prepared(request=req, state=state, sched=sched, dev=dev,
                         arr=arr, key=key, criterion=crit,
                         budget=crit.budget(sess.epochs))

    # -- the serving loop --------------------------------------------------

    def _admit_arrived(self) -> int:
        # capacity accounting is scoped to ONE admission scan: slots
        # handed out earlier in the scan are reserved so a burst of
        # same-bucket arrivals never overfills a group
        reserved: Dict[Hashable, int] = {}

        def capacity(key: Hashable) -> bool:
            group = self._groups.get(key)
            if group is not None:
                free = sum(s is None for s in group.slots)
            else:
                new = {k for k in reserved if k not in self._groups}
                if self.max_groups is not None and key not in new \
                        and len(self._groups) + len(new) >= self.max_groups:
                    return False
                free = self.lane_width
            if reserved.get(key, 0) >= free:
                return False
            reserved[key] = reserved.get(key, 0) + 1
            return True

        admitted = self._scheduler.pop_admissible(self.now, capacity)
        for req, key in admitted:
            prep = self._prepared[req.uid]
            group = self._groups.get(key)
            if group is None:
                group = _LaneGroup(self, key, prep)
                self._groups[key] = group
            group.admit(self, prep, group.free_slot())
        return len(admitted)

    def step(self) -> List[TraceReport]:
        """One engine iteration: admit everything that has arrived (whole
        queue scan — no head-of-line blocking), advance every busy group
        one chunk, harvest finished lanes.  Returns the harvest."""
        self._admit_arrived()
        if not any(g.running for g in self._groups.values()):
            nxt = self._scheduler.next_arrival(self.now)
            if nxt is not None:  # idle: fast-forward to the next arrival
                self.now = nxt
                self._admit_arrived()
        harvested: List[TraceReport] = []
        for group in self._groups.values():
            if not group.running:
                continue
            for _, prep, trace, t_exit, conv, beta in group.step():
                report = self._report(prep, trace, t_exit, conv, beta)
                self._done[prep.request.uid] = report
                del self._prepared[prep.request.uid]
                harvested.append(report)
        self.steps += 1
        self.now += self.chunk
        return harvested

    def drain(self, max_steps: int = 100_000) -> List[TraceReport]:
        """Serve until queue and lanes are empty; reports in submit
        order."""
        for _ in range(max_steps):
            if not len(self._scheduler) and \
                    not any(g.running for g in self._groups.values()):
                break
            self.step()
        else:
            raise RuntimeError(f"drain did not finish in {max_steps} steps")
        return [self._done[uid] for uid in self._uids if uid in self._done]

    def serve(self, sessions: Sequence[Session], *,
              arrivals: Optional[Sequence[float]] = None,
              states: Optional[Sequence[Any]] = None) -> List[TraceReport]:
        """Admit everything, drain: the batch entry point.  Reports come
        back in `sessions` order regardless of arrival interleaving."""
        uids = self.submit_many(sessions, arrivals=arrivals, states=states)
        self.drain()
        return [self._done[uid] for uid in uids]

    # -- reporting ---------------------------------------------------------

    def _report(self, prep: _Prepared, trace: np.ndarray, t_exit: int,
                converged: bool,
                beta: Optional[np.ndarray] = None) -> TraceReport:
        """Assemble the truncated-run TraceReport: a PREFIX of the solo
        report up to the exit epoch, with the early-exit point (and a
        correspondingly truncated privacy schedule) on `extras`."""
        sess = prep.request.session
        sched = prep.sched
        durations = np.asarray(sched.durations)[:t_exit]
        times = sched.t0 + np.concatenate([[0.0], np.cumsum(durations)])
        extras_fn = getattr(sess.strategy, "report_extras", None)
        extras = dict(extras_fn(prep.state)) if extras_fn is not None else {}
        eps_sched = extras.get("epsilon_schedule")
        if eps_sched is not None and t_exit < len(np.asarray(eps_sched)):
            # an early-exited lane only SPENDS the rounds it ran: the
            # cumulative schedule and composed total truncate with it
            cut = np.asarray(eps_sched)[:t_exit]
            extras["epsilon_schedule"] = cut
            extras["epsilon_spent"] = float(cut[-1]) if t_exit else 0.0
            extras["accounting_rounds"] = int(t_exit)
        extras["serve_exit_epoch"] = int(t_exit)
        extras["serve_converged"] = bool(converged)
        extras["serve_uid"] = int(prep.request.uid)
        return TraceReport(
            times=times,
            nmse=np.asarray(trace)[:t_exit + 1],
            epoch_durations=durations,
            label=sess.strategy.label,
            setup_time=sched.setup_time,
            uplink_bits_total=sess.strategy.uplink_bits(
                prep.state, sess.fleet, t_exit),
            extras=extras,
            beta=beta)

    # -- introspection -----------------------------------------------------

    @property
    def n_groups(self) -> int:
        return len(self._groups)

    @property
    def n_pending(self) -> int:
        return len(self._scheduler)

    @property
    def n_active(self) -> int:
        return sum(sum(s is not None for s in g.slots)
                   for g in self._groups.values())
