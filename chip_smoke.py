"""Smoke run of the CFL training path on a TPU.

    python chip_smoke.py             # phases a-e on one chip
    python chip_smoke.py --chips 4   # the multi-chip phase on four chips

Drives the paper's §IV deployment (n = 24 clients, ell = 300 rows each,
d = 500, m = 7,200; `paper_fleet(0.2, 0.2, seed=0)`, `TrainData.linreg`)
through the entry points a user calls: `CodedFL.plan`, `Session.run`,
`run_sweep` and `FedServeEngine`.  Every phase prints one line with its
wall time, the part of it spent compiling, and the check it passed:

  a. device, JAX version, compile cache, tiles the kernels resolve;
  b. planning vs the float64 NumPy oracle, then solo training of
     UncodedFL and CodedFL: finite traces, a 10x NMSE drop, and the
     Pallas kernel (`tpu_custom_call`) present in the compiled engine;
  c. a 4-lane delta sweep, each lane bit-equal to its solo run;
  d. 4 sessions served by `FedServeEngine`, each a prefix of its solo run;
  e. a 2-tier `HierarchicalCFL(CodedFL)` session (the tier kernel).

`--chips 4` runs only what exists across chips: a 16-lane delta sweep on
a 4-device lane mesh vs the same lanes run one device at a time, and the
mesh-sharded fleet planner at 1e4 clients vs the batched solver.

The last line of standard output is one JSON object,
`{"ok": true, "device": {...}}`.  Any failed check, or a machine where
JAX finds no TPU, ends the run with a non-zero exit and no such line.
All phases run in this one process, which holds the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import (CodedFL, Session, TrainData, UncodedFL,  # noqa: E402
                       make_strategy, plan_sweep, run_sweep)
from repro.fleet import FleetTopology, HierarchicalCFL, solve_fleet  # noqa
from repro.kernels.encode import encode as enc_k  # noqa: E402
from repro.kernels.round_grad import round_grad as rg_k  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.plan import PlanRequest, solve_redundancy_batched  # noqa: E402
from repro.plan.reference import solve_redundancy_reference  # noqa: E402
from repro.serving import ConvergenceCriterion, FedServeEngine  # noqa: E402
from repro.sim.network import mega_fleet, paper_fleet  # noqa: E402
from repro.tune import cache as tile_cache  # noqa: E402

N, ELL, D = 24, 300, 500
M = N * ELL
C = int(0.28 * M)
LR = 0.0085
EPOCHS = 200
DELTAS = (0.1, 0.2, 0.28, 0.4)
FLEET_N = 10_000


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


class Phase:
    """Times one phase.  Its compile time is the XLA compilation JAX's
    monitoring events report while the phase runs (reads of the
    persistent cache included); the rest is tracing, host work and the
    device."""

    _compile_s = 0.0
    _listening = False

    @classmethod
    def _listen(cls):
        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                cls._compile_s += secs

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        cls._listening = True

    def __init__(self, name: str):
        self.name = name
        if not Phase._listening:
            Phase._listen()

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = Phase._compile_s
        return self

    def done(self, passed: str) -> None:
        wall = time.perf_counter() - self.t0
        comp = Phase._compile_s - self.c0
        print(f"[{self.name}] wall {wall:.2f}s (compile {comp:.2f}s, "
              f"rest {wall - comp:.2f}s) | passed: {passed}", flush=True)

    def __exit__(self, *exc):
        return False


def same_trace(a, b) -> bool:
    return (np.array_equal(a.nmse, b.nmse)
            and np.array_equal(a.times, b.times)
            and np.array_equal(a.epoch_durations, b.epoch_durations))


def engine_texts(session) -> list:
    """Compiled program text of each engine the session ran."""
    return [engine.as_text() for engine in session._engines.values()]


def phase_device():
    with Phase("a. device") as ph:
        cache_dir = use_compile_cache()
        dev = jax.devices()[0]
        print(f"  platform={dev.platform} kind={dev.device_kind} "
              f"count={len(jax.devices())} jax={jax.__version__}")
        print(f"  compile cache: {cache_dir}")
        for family, shape, default in (
                ("round_grad", (M, D), rg_k.DEFAULT_BLOCK_M),
                ("encode", (C, ELL, D), enc_k.DEFAULT_BLOCK),
                ("encode_prng", (C, ELL, D), enc_k.DEFAULT_BLOCK)):
            source, block = "kernel default", default
            for label, path in (("user cache", tile_cache.user_cache_path()),
                                ("repo defaults",
                                 tile_cache.defaults_path())):
                hit = path and tile_cache.TileCache(path).lookup(
                    family, shape, dev.platform)
                if hit:
                    source, block = label, tuple(hit["block"])
                    break
            print(f"  tile {family}{shape}: {block} from {source}")
        ph.done(f"platform is tpu, cache at {cache_dir}")


def phase_solo(fleet, data):
    with Phase("b. plan") as ph:
        coded = CodedFL(key=jax.random.PRNGKey(1), fixed_c=C)
        state = coded.plan(fleet, data)
        # the oracle bisects only until its bracket is eps_rel wide, and
        # takes its loads at that bracket's end; the grid solver's t* sits
        # far closer to the root, so the oracle runs one decade tighter
        # (as in tests/test_plan_solver.py)
        ref = solve_redundancy_reference(fleet.edge, fleet.server,
                                         np.full(N, ELL), fixed_c=C,
                                         eps_rel=1e-4)
        rel = abs(state.plan.t_star - ref.t_star) / ref.t_star
        check(rel <= 1e-3, f"t* {state.plan.t_star} vs oracle {ref.t_star}")
        check(np.array_equal(state.plan.loads, ref.loads),
              "plan loads differ from the oracle")
        ph.done(f"float64 plan on the default device "
                f"({jax.devices()[0].device_kind}): t*={state.plan.t_star!r}"
                f" vs oracle {ref.t_star!r} (rel {rel:.2e} <= 1e-3), loads "
                f"identical, c={state.c}")

    for name, strat, st in (("UncodedFL", UncodedFL(), None),
                            ("CodedFL", coded, state)):
        with Phase(f"b. solo {name}") as ph:
            session = Session(strategy=strat, fleet=fleet, lr=LR,
                              epochs=EPOCHS)
            rep = session.run(data, rng=np.random.default_rng(0), state=st)
            check(bool(np.all(np.isfinite(rep.nmse))), f"{name}: non-finite")
            check(any("tpu_custom_call" in t for t in engine_texts(session)),
                  f"{name}: no tpu_custom_call in the compiled engine")
            drop = rep.nmse[0] / rep.nmse[-1]
            if name == "CodedFL":
                check(drop >= 10.0, f"CodedFL NMSE fell only {drop:.2f}x")
            ph.done(f"{EPOCHS} epochs finite, NMSE {rep.nmse[0]:.3e} -> "
                    f"{rep.nmse[-1]:.3e} ({drop:.1f}x), "
                    "tpu_custom_call in engine")


def delta_sessions(fleet, deltas):
    return [Session(strategy=make_strategy("cfl", key_seed=1,
                                           fixed_c=int(dl * M)),
                    fleet=fleet, lr=LR, epochs=EPOCHS, seed=i)
            for i, dl in enumerate(deltas)]


def solo_run(session, data, state=None):
    return session.run(data, rng=np.random.default_rng(session.seed),
                       state=state)


def phase_sweep(fleet, data):
    with Phase("c. sweep") as ph:
        sessions = delta_sessions(fleet, DELTAS)
        swept = run_sweep(sessions, data)
        for s, rep in zip(sessions, swept):
            check(same_trace(rep, solo_run(s, data)),
                  f"sweep lane c={s.strategy.fixed_c} != its solo run")
        ph.done(f"{len(sessions)} lanes (delta {DELTAS}) bit-equal to solo")


def phase_serve(fleet, data):
    with Phase("d. serve") as ph:
        sessions = delta_sessions(fleet, DELTAS)
        engine = FedServeEngine(
            data, lane_width=4, chunk=25,
            criterion=ConvergenceCriterion(nmse_target=1e-2))
        served = engine.serve(sessions)
        exits = []
        for s, rep in zip(sessions, served):
            t = rep.extras["serve_exit_epoch"]
            solo = solo_run(s, data)
            check(np.array_equal(rep.nmse, solo.nmse[:t + 1])
                  and np.array_equal(rep.times, solo.times[:t + 1]),
                  f"served c={s.strategy.fixed_c} is not a prefix of solo")
            exits.append(t)
        ph.done(f"{len(served)} served traces prefix-equal to solo up to "
                f"exit epochs {exits}")


def phase_tiers(fleet, data):
    with Phase("e. tiers") as ph:
        strat = HierarchicalCFL(base=CodedFL(key=jax.random.PRNGKey(1),
                                             fixed_c=C),
                                topology=FleetTopology.uniform(N, 2))
        session = Session(strategy=strat, fleet=fleet, lr=LR, epochs=EPOCHS)
        rep = session.run(data, rng=np.random.default_rng(0))
        check(bool(np.all(np.isfinite(rep.nmse))), "tiers: non-finite")
        check(any("tpu_custom_call" in t for t in engine_texts(session)),
              "tiers: no tpu_custom_call in the compiled engine")
        ph.done(f"2-tier CodedFL NMSE {rep.nmse[0]:.3e} -> "
                f"{rep.nmse[-1]:.3e}, tpu_custom_call in engine")


def phase_four_chips(fleet, data):
    check(len(jax.devices()) == 4, f"{len(jax.devices())} devices, not 4")
    with Phase("4chips. sweep") as ph:
        # two shape buckets of 8 lanes each (the packed systematic rows
        # change size at delta ~0.265), so every engine spans 4 devices
        deltas = tuple(round(0.19 + 0.01 * i, 2) for i in range(16))
        sessions = delta_sessions(fleet, deltas)
        # one batched plan for all lanes, reused by the one-device runs:
        # each distinct request shape compiles the float64 planner again
        states = plan_sweep(sessions, data)
        swept = run_sweep(sessions, data, states=states)
        for engine in {id(e): e for s in sessions
                       for e in s._engines.values()}.values():
            for i, sh in enumerate(jax.tree.leaves(engine.output_shardings)):
                print(f"  sweep output {i}: {sh}")
                check(len(sh.device_set) == 4,
                      f"sweep output on {len(sh.device_set)} devices")
        for s, st, rep in zip(sessions, states, swept):
            check(same_trace(rep, solo_run(s, data, st)),
                  f"4-device lane c={s.strategy.fixed_c} != one-device run")
        ph.done(f"{len(sessions)} lanes over 4 devices bit-equal to the "
                "same lanes run one device at a time")

    with Phase("4chips. fleet plan") as ph:
        big = mega_fleet(FLEET_N, d=32, seed=0)
        sizes = np.random.default_rng(1).integers(4, 17, size=FLEET_N)
        req = PlanRequest(edge=big.edge, server=big.server,
                          data_sizes=sizes, c_up=4096)
        sharded = solve_fleet(req, eps_rel=1e-6)
        batched = solve_redundancy_batched([req], eps_rel=1e-6)[0]
        rel = abs(sharded.t_star - batched.t_star) / batched.t_star
        check(rel <= 1e-4, f"fleet t* {sharded.t_star} vs {batched.t_star}")
        same_loads = bool(np.array_equal(sharded.loads, batched.loads))
        check(same_loads or sharded.t_star != batched.t_star,
              "fleet loads differ at equal t*")
        ph.done(f"solve_fleet n={FLEET_N} over {len(jax.devices())} "
                f"devices: t* rel diff {rel:.2e} <= 1e-4, loads "
                f"{'equal' if same_loads else 'differ (t* differs)'}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip phase")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform})",
              file=sys.stderr)
        return 1

    with Phase("all") as total:
        try:
            phase_device()
            fleet = paper_fleet(nu_comp=0.2, nu_link=0.2, seed=0)
            data = TrainData.linreg(jax.random.PRNGKey(0), N, ELL, D)
            if args.chips == 4:
                phase_four_chips(fleet, data)
            else:
                phase_solo(fleet, data)
                phase_sweep(fleet, data)
                phase_serve(fleet, data)
                phase_tiers(fleet, data)
        except CheckFailed as e:
            print(f"chip_smoke: check failed: {e}", file=sys.stderr)
            return 1
        total.done("every check")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
