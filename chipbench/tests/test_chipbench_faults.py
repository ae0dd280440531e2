"""A run of the harness with the timed path broken underneath comes out
not correct: once for each fault the cells can have (on the CPU, at the
cut size of `cut.py`, with the look for a chip skipped).

  unchanged  the epoch step returns the model unchanged
  half       half of the rows left out of the round gradient, the sum
             over the rest doubled
  altered    every lane's answer altered where it is produced (the first
             coordinate of its final model, by 0.1%)
  exchange   (sweeps) the lanes another device would compute never come
             back: each is a copy of the first lane
"""
import numpy as np
import pytest

import cut


@pytest.fixture(autouse=True)
def fresh_engines():
    from repro.api import session

    session._ENGINE_CACHE.clear()
    yield
    session._ENGINE_CACHE.clear()


def _unchanged(mp):
    from repro.core import aggregation

    mp.setattr(aggregation, "gd_update", lambda beta, g, lr, m: beta)


def _half(mp):
    from repro.core import aggregation

    orig = aggregation.round_gradient

    def half(x, y, beta, w=None, path=aggregation.REFERENCE):
        k = x.shape[0] // 2
        return 2.0 * orig(x[:k], y[:k], beta,
                          None if w is None else w[:k], path)

    mp.setattr(aggregation, "round_gradient", half)


def _altered(mp):
    from repro.api import session

    orig = session._lane_report

    def altered(*a, **kw):
        rep = orig(*a, **kw)
        rep.beta = np.array(rep.beta)
        rep.beta[0] *= 1.001
        return rep

    mp.setattr(session, "_lane_report", altered)


def _exchange(mp):
    from repro.api import session

    orig = session._execute_lanes

    def first_lane_only(entries, data):
        out = orig(entries, data)
        return [out[0]] * len(out)

    mp.setattr(session, "_execute_lanes", first_lane_only)


FAULTS = {"unchanged": _unchanged, "half": _half, "altered": _altered,
          "exchange": _exchange}
SOLO = ("unchanged", "half", "altered")
CASES = [("cfl_sec4", "solo_alternate", f) for f in SOLO] \
    + [("cfl_sec4", "sweep16", f) for f in FAULTS]


@pytest.mark.parametrize("config, traffic, fault", CASES)
def test_fault_is_not_correct(monkeypatch, config, traffic, fault):
    FAULTS[fault](monkeypatch)
    result = cut.run("cut_" + traffic, traffic, seed=3_000_000_029,
                     cfg=cut.config(config))
    assert result is not None
    assert not result["correct"], result["checks"]
