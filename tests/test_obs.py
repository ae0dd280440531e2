"""The program's host spans and counters (`repro.obs`), read back from a
CPU profiler trace the way the chip benchmark reads them."""
import glob
import os

import jax
import numpy as np
import pytest

from repro import obs
from repro.api import Session, TrainData, make_strategy, run_sweep
from repro.serving import FedServeEngine
from repro.sim.network import paper_fleet, wireless_fleet

EPOCHS = 12

# every span that opens inside another, with the span that holds it
PARENT = {"repro.solve": "repro.plan", "repro.encode": "repro.plan",
          "repro.sample": "repro.run", "repro.stage": "repro.run",
          "repro.engine": "repro.run", "repro.fetch": "repro.run",
          "repro.report": "repro.run", "repro.build": "repro.engine"}


@pytest.fixture(scope="module")
def small():
    fleet = paper_fleet(0.2, 0.2, seed=1, n=8, d=24)
    data = TrainData.linreg(jax.random.PRNGKey(0), n=8, ell=40, d=24)
    return fleet, data


def _cfl(fleet, data, seed):
    c = int(0.3 * data.m)
    return Session(strategy=make_strategy("cfl", key_seed=seed, fixed_c=c),
                   fleet=fleet, lr=0.05, epochs=EPOCHS, seed=seed)


def _traced(tmp_path, fn):
    """Run `fn` under the profiler; return the program's host spans as
    (name, start, end, stats) sorted by start, and what `fn` returned."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    found = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    found.append((e.name, float(e.start_ns),
                                  float(e.start_ns + e.duration_ns),
                                  {k: v for k, v in e.stats}))
    return sorted(found, key=lambda s: (s[1], -s[2])), out


def _named(found, name):
    return [s for s in found if s[0] == name]


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_solo_session_spans_nest_by_layer(tmp_path, small):
    fleet, data = small
    sess = _cfl(fleet, data, 7)

    def solo():
        state = sess.plan(data)
        return sess.run(data, rng=np.random.default_rng(0), state=state)

    found, report = _traced(tmp_path, solo)
    assert len(report.nmse) == EPOCHS + 1
    names = {s[0] for s in found}
    assert names >= {"repro.plan", "repro.solve", "repro.encode",
                     "repro.run", "repro.sample", "repro.stage",
                     "repro.engine", "repro.fetch", "repro.report"}
    assert names <= set(obs.SPANS)
    for child in found:
        if child[0] in PARENT:
            holders = _named(found, PARENT[child[0]])
            assert any(_inside(child, p) for p in holders), child
    sample, = _named(found, "repro.sample")
    assert sample[3] == {"lanes": 1, "epochs": EPOCHS}
    run, = _named(found, "repro.run")
    assert run[3] == {"lanes": 1}
    assert _named(found, "repro.encode")[0][3]["c"] == int(0.3 * data.m)
    engine, = _named(found, "repro.engine")
    assert engine[3]["lanes"] == 1 and engine[3]["devices"] == 1
    # an engine compiled in this call shows as a build inside its span
    assert engine[3]["builds"] == len(_named(found, "repro.build"))


def test_sweep_spans_count_lanes(tmp_path, small):
    fleet, data = small
    sessions = [_cfl(fleet, data, seed) for seed in (11, 12, 13)]
    found, reports = _traced(tmp_path, lambda: run_sweep(sessions, data))
    assert len(reports) == 3
    run, = _named(found, "repro.run")
    assert run[3] == {"lanes": 3}
    plan, = _named(found, "repro.plan")
    assert plan[3] == {"sessions": 3} and _inside(plan, run)
    solve, = _named(found, "repro.solve")
    assert solve[3] == {"requests": 3}
    sample, = _named(found, "repro.sample")
    assert sample[3] == {"lanes": 3, "epochs": EPOCHS}
    assert _named(found, "repro.report")[0][3] == {"lanes": 3}
    assert sum(s[3]["lanes"] for s in _named(found, "repro.fetch")) == 3
    # every chunk is dispatched before the one fetch of all outputs
    fetch, = _named(found, "repro.fetch")
    assert all(e[2] <= fetch[1] for e in _named(found, "repro.engine"))
    assert {s[0] for s in found} <= set(obs.SPANS)


def test_serve_drain_spans(tmp_path, small):
    fleet, data = small
    sessions = [_cfl(fleet, data, seed) for seed in (21, 22)]
    engine = FedServeEngine(data, lane_width=2, chunk=5)
    found, reports = _traced(tmp_path, lambda: engine.serve(sessions))
    assert len(reports) == 2
    steps = _named(found, "repro.serve.step")
    assert len(steps) == engine.steps
    admits = _named(found, "repro.serve.admit")
    assert sum(s[3]["admitted"] for s in admits) == 2
    assert all(any(_inside(a, s) for s in steps) for a in admits)
    assert sum(s[3]["groups"] for s in steps) >= 1
    assert len(_named(found, "repro.sample")) == 2
    assert _named(found, "repro.fetch")
    assert {s[0] for s in found} <= set(obs.SPANS)


def test_span_names_are_checked():
    with pytest.raises(ValueError, match="unknown span"):
        obs.span("repro.nothing")
    assert all(name.startswith("repro.") for name in obs.SPANS)
    assert len(set(obs.SPANS)) == len(obs.SPANS)


def test_engine_cache_counters(monkeypatch, small):
    """Two shape buckets through a one-engine cache: each lookup builds,
    and each build past the first evicts."""
    from repro.api.session import _ENGINE_CACHE

    fleet, data = small
    saved = dict(_ENGINE_CACHE)
    _ENGINE_CACHE.clear()
    try:
        monkeypatch.setenv("REPRO_ENGINE_CACHE_MAX", "1")
        coded = _cfl(fleet, data, 31)
        uncoded = Session(strategy=make_strategy("uncoded"), fleet=fleet,
                          lr=0.05, epochs=EPOCHS)
        before = obs.counters()
        for sess in (coded, uncoded, coded):
            sess.run(data, rng=np.random.default_rng(1))
        after = obs.counters()
        assert after["engine_builds"] - before["engine_builds"] == 3
        assert after["engine_evictions"] - before["engine_evictions"] == 2
        # one lane, one chunk: nothing split
        assert after["lane_buckets_split"] == before["lane_buckets_split"]
        assert len(_ENGINE_CACHE) == 1
    finally:
        _ENGINE_CACHE.clear()
        _ENGINE_CACHE.update(saved)
    # a copy: changing it changes nothing
    after["engine_builds"] = -1
    assert obs.counters()["engine_builds"] >= 3


@pytest.mark.parametrize("strategy, fleet_kind, uniform, mixed", [
    ("cfl", "paper", 2, 0),        # edge fleet, then the tau = 0 server
    ("uncoded", "paper", 1, 0),
    ("cfl", "wireless", 1, 1),     # per-device erasure on the edge
])
def test_sample_group_counters(small, strategy, fleet_kind, uniform, mixed):
    """One solo session counts each device group its epochs draw, by
    whether the group's geometric draws take one success probability."""
    _, data = small
    if fleet_kind == "paper":
        fleet = paper_fleet(0.2, 0.2, seed=1, n=data.n, d=data.d)
    else:
        fleet = wireless_fleet(0.2, 0.2, nu_erasure=0.3, seed=1, n=data.n,
                               d=data.d)
    if strategy == "cfl":
        sess = _cfl(fleet, data, 41)
    else:
        sess = Session(strategy=make_strategy("uncoded"), fleet=fleet,
                       lr=0.05, epochs=EPOCHS)
    state = sess.plan(data)
    before = obs.counters()
    sess.run(data, rng=np.random.default_rng(2), state=state)
    after = obs.counters()
    assert (after["sample_groups_uniform"]
            - before["sample_groups_uniform"]) == uniform
    assert (after["sample_groups_mixed"]
            - before["sample_groups_mixed"]) == mixed
