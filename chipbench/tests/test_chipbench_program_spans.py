"""The readers of the program's own spans (`program_spans.py`): on a cut
`sec4_solo` traced on the CPU through the harness, and `idle_unspanned`
by hand on a summary with known intervals."""
import copy

import pytest

import cut
import program_spans as ps
import run
import trace_reduce as tr

HOST_METRICS = ("sample_us.solo", "stage_ms.solo", "fetch_ms.solo",
                "solve_ms.solo")
CHILDREN = ("repro.sample", "repro.stage", "repro.engine", "repro.fetch",
            "repro.report")
WORKLOAD = "spans_cut"


@pytest.fixture(autouse=True)
def fresh_engines():
    from repro.api import session

    session._ENGINE_CACHE.clear()
    yield
    session._ENGINE_CACHE.clear()


def _bench():
    """The cut cell, with the five metrics read from the program's
    spans."""
    bench = cut.bench(WORKLOAD, "solo_alternate")
    for name in HOST_METRICS + ("idle_unspanned",):
        bench["per_layer"].append({"name": name, "unit": "ms",
                                   "moves": "setup_s",
                                   "workloads": [WORKLOAD]})
    return bench


def test_readers_on_a_cpu_traced_cut_cell():
    result = run.run_cell(WORKLOAD, 3141592653, 1.0, True,
                          require_tpu=False,
                          config=copy.deepcopy(cut.config()),
                          traffic=cut.traffic("solo_alternate"),
                          bench=_bench())
    assert result["correct"]
    metrics = result["metrics"]
    for name in HOST_METRICS:
        assert metrics[name]["value"] > 0, name
    # no device plane on the CPU: nothing to be idle against
    assert "idle_unspanned" not in metrics

    path = ps.newest_trace()
    window = tr.reduce_trace(path, run.SPANS).window
    found = ps.load(path, ps.span_names(), window)
    runs = ps.named(found, "repro.run")
    assert runs
    for r in runs:
        inside = [s for s in found if s.name in CHILDREN
                  and r.start <= s.start and s.end <= r.end]
        assert {s.name for s in inside} == set(CHILDREN)
        assert sum(s.ns for s in inside) <= r.ns
    sample = ps.named(found, "repro.sample")
    assert all(s.counts == {"lanes": 1, "epochs": cut.EPOCHS}
               for s in sample)
    assert 0 < ps.run_cover(found) <= 100


def _span(name, start, end, **counts):
    return ps.Span(name, float(start), float(end), counts)


def test_idle_unspanned_by_hand():
    ops = [("op", 10.0, 20.0), ("op", 60.0, 70.0)]
    dev = tr.Device(0, ops, tr.merge((s, e) for _, s, e in ops))
    summary = tr.TraceSummary(window=(0.0, 100.0), devices=[dev],
                              spans={"window": [(0.0, 100.0)]})
    found = [_span("repro.run", 5.0, 50.0, lanes=1),
             _span("repro.sample", 5.0, 30.0, lanes=1, epochs=4),
             _span("repro.fetch", 35.0, 45.0, lanes=1),
             _span("repro.run", 55.0, 90.0, lanes=1)]
    # busy (10, 20) (60, 70); spans (5, 50) (55, 90): idle and outside
    # them are (0, 5), (50, 55) and (90, 100)
    assert ps.idle_unspanned(summary, found) == [pytest.approx(20.0)]
    names, secs = zip(*ps.idle_gaps(summary, found))
    # (20, 60) has its middle, 40, in the fetch; (70, 100) at 85 in the
    # second run; (0, 10) at 5 opens the first run and its sample
    assert names == ("repro.fetch", "repro.run", "repro.sample")
    assert secs == pytest.approx((40e-9, 30e-9, 10e-9))
    # the first run's children cover 25 + 10 of its 45, the second's 0
    assert ps.run_cover(found) == pytest.approx(100.0 * 35.0 / 80.0)


def test_a_program_without_spans_reads_nothing(monkeypatch):
    monkeypatch.setattr(ps, "span_names", lambda: None)
    ctx = run.Ctx(system=None, traffic={}, seed=0, chips=1)
    ctx.trace = tr.TraceSummary(window=(0.0, 1.0), devices=[], spans={})
    for name in HOST_METRICS + ("idle_unspanned",):
        assert run.find_reader(name).read(ctx, name) is None
