"""The float64 reference against the program, and its control, on the CPU
at a cut size (`cut.py`).

The program's sessions (`Session.run`, and `run_sweep` lanes) meet the
configuration's limits; the reference computed one step below the
stated precision (matrix products in three bfloat16 passes, or the data
rounded to bfloat16) does not.
"""
import dataclasses

import ml_dtypes
import numpy as np
import pytest

import checks
import cut
import deploy
from reference import FLOAT64, HIGH


@pytest.fixture(scope="module", params=["cfl_sec4", "codedfedl_mnist"])
def system(request):
    return deploy.build(cut.config(request.param), seed=3_000_000_017)


@pytest.mark.parametrize("config, traffic", [
    ("cfl_sec4", "solo_alternate"), ("cfl_sec4", "sweep16"),
    ("codedfedl_mnist", "solo_cfedl")])
def test_program_meets_the_limits(config, traffic):
    cfg = cut.config(config)
    result = cut.run("cut_" + traffic, traffic, seed=2_147_483_659,
                     cfg=cfg)
    assert set(result["checks"]) == set(cfg["limits"])
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0


def _verdict(system, answers):
    return checks.verdict(system.cfg, [checks.compare(system, a)
                                       for a in answers])


def _answers(system, ar):
    names = sorted(system.cfg["strategies"])
    return [checks.reference_answer(system, names[i % len(names)],
                                    key=11 + i, rng=21 + i, overrides={},
                                    ar=ar)
            for i in range(3)]


def test_float64_reference_is_exact_against_itself(system):
    verdict, ok = _verdict(system, _answers(system, FLOAT64))
    assert ok, verdict
    assert all(v["value"] <= 1e-12 for k, v in verdict.items()
               if k != "t_star_gap")


def test_control_in_three_bf16_passes_fails(system):
    verdict, ok = _verdict(system, _answers(system, HIGH))
    assert not ok
    failed = [k for k, v in verdict.items() if v["value"] > v["limit"]]
    assert failed, verdict


def test_control_on_bf16_data_fails(system):
    xs, ys, bt = checks._host_data(system)
    low = dataclasses.replace(system, cache={})
    low.cache["host"] = tuple(
        a.astype(ml_dtypes.bfloat16).astype(np.float32)
        for a in (xs, ys)) + (bt,)
    answers = _answers(low, FLOAT64)
    verdict, ok = _verdict(system, answers)
    assert not ok, verdict
