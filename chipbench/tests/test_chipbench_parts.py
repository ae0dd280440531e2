"""A configuration's parts, found by name (`datasets/`, `fleets/`,
`reference/`), give the same bits as the harness did when it chose them
in `deploy.py` and `checks.py`: the data, the reference fleet, one
reference answer per strategy and the count at one output column, pinned
by hash at the cut size of `cut.py` on the CPU."""
import dataclasses
import hashlib
import types

import numpy as np
import pytest

import checks
import cut
import deploy
import traffic_common
from reference import FLOAT64

SEED = 3_000_000_017

PINS = {
    "cfl_sec4": {
        "data": {"xs": "ba2020ebc308468a", "ys": "b4e4f13cf1b4f794",
                 "beta_true": "2e5e8277d92bdec0"},
        "ref_fleet": {"a": "d6f34cd85da2ea4a", "mu": "07b3e0857c7c1a97",
                      "tau": "1e1f93c45bf4a1ae", "p": "08bb368c8480ca43",
                      "a_srv": "92bc5dc0c9267a57",
                      "mu_srv": "b60b87eb35af0b7e",
                      "link_rates": "38500db9e4df4706",
                      "packet_bits": "c196ea42aa2f00d7"},
        "answers": {
            "cfl": {"nmse": "f94838b4ff3e8f9b", "beta": "6f2bc8d5005d058f",
                    "times": "e7506c95cb05ea2c",
                    "t_star": "7330baec2d149bf1",
                    "loads": "482fec32e4afefa7",
                    "p_return": "4877bc94686798cd",
                    "parity": "4dbbb8a78c65a240"},
            "uncoded": {"nmse": "d0e2d105a5554c52",
                        "beta": "98477341b6250352",
                        "times": "e1e1b26c10e03374"}},
        # count.Work fields: sys_flops, sys_bytes, par_flops, par_bytes,
        # resident
        "work": {"cfl": (737024.0, 783088.0, 30720.0, 65280.0, 14348.0),
                 "uncoded": (921600.0, 979200.0, 0.0, 0.0, 16320.0)},
    },
    "codedfedl_mnist": {
        "data": {"xs": "ba2020ebc308468a", "ys": "6ff7b140391263a6",
                 "beta_true": "ef1538fe0d47cc3f"},
        "ref_fleet": {"a": "ba00382e0218da3d", "mu": "370fe853dc35b4d0",
                      "tau": "a5f47a4c94195a0a", "p": "b9bcee5fd1b458a9",
                      "a_srv": "1674cfccc19edb06",
                      "mu_srv": "ecc8cfa85135d1b2",
                      "link_rates": "57ba2ef79a6741ed",
                      "packet_bits": "69d82b3e1d71e1ad"},
        "answers": {
            "cfedl": {"nmse": "ff723d5c06acbf39",
                      "beta": "bc9015e348dd4e0b",
                      "times": "53433a3db23d296b",
                      "t_star": "7f71e707884b4811",
                      "loads": "4d80d3c3c3c7ba48",
                      "p_return": "b86d0521caa91968",
                      "parity": "27f7a175ebb03e95"}},
    },
}


def _hash(a) -> str:
    a = np.ascontiguousarray(np.asarray(a))
    return hashlib.sha256(str(a.dtype).encode() + str(a.shape).encode()
                          + a.tobytes()).hexdigest()[:16]


@pytest.fixture(scope="module")
def systems():
    return {name: deploy.build(cut.config(name), seed=SEED)
            for name in PINS}


def _answer(system, i, name):
    return checks.reference_answer(system, name, key=11 + i, rng=21 + i,
                                   overrides={}, ar=FLOAT64)


@pytest.mark.parametrize("config", sorted(PINS))
def test_data_is_the_parents(systems, config):
    data = systems[config].data
    assert {k: _hash(getattr(data, k)) for k in PINS[config]["data"]} \
        == PINS[config]["data"]
    assert systems[config].sizes.tolist() == [cut.ELL] * cut.N


@pytest.mark.parametrize("config", sorted(PINS))
def test_reference_fleet_is_the_parents(systems, config):
    fleet = systems[config].ref_fleet
    assert {k: _hash(np.asarray(getattr(fleet, k), np.float64))
            for k in PINS[config]["ref_fleet"]} == PINS[config]["ref_fleet"]


@pytest.mark.parametrize("config, strategy", [
    (c, s) for c in sorted(PINS) for s in sorted(PINS[c]["answers"])])
def test_reference_answer_is_the_parents(systems, config, strategy):
    system = systems[config]
    i = sorted(system.cfg["strategies"]).index(strategy)
    ans = _answer(system, i, strategy)
    pins = PINS[config]["answers"][strategy]
    assert {k: _hash(getattr(ans, k)) for k in pins} == pins


@pytest.mark.parametrize("strategy", sorted(PINS["cfl_sec4"]["work"]))
def test_count_at_one_output_is_the_parents(systems, strategy):
    system = systems["cfl_sec4"]
    i = sorted(system.cfg["strategies"]).index(strategy)
    ans = _answer(system, i, strategy)
    plan = None if ans.loads is None else types.SimpleNamespace(
        loads=ans.loads, c=ans.parity.shape[0], t_star=ans.t_star,
        p_return=ans.p_return)
    ctx = types.SimpleNamespace(system=system)
    work = traffic_common.work(ctx, [{"sessions": [(strategy, 21 + i,
                                                    plan)]}])
    assert dataclasses.astuple(work) == PINS["cfl_sec4"]["work"][strategy]


def test_a_fixed_fleet_seed_keeps_the_fleet_under_every_seed():
    # a traffic mix's `fleet_seed` draws one fleet for every --seed; the
    # data still comes from --seed, and without it the fleet does too
    cfg = cut.config("cfl_sec4")
    a = deploy.build(cfg, seed=SEED + 1, fleet_seed=SEED)
    b = deploy.build(cfg, seed=SEED + 2, fleet_seed=SEED)
    own = deploy.build(cfg, seed=SEED + 1)
    for k in PINS["cfl_sec4"]["ref_fleet"]:
        assert _hash(np.asarray(getattr(a.ref_fleet, k), np.float64)) \
            == PINS["cfl_sec4"]["ref_fleet"][k]
        assert np.array_equal(getattr(a.ref_fleet, k),
                              getattr(b.ref_fleet, k))
    assert not np.array_equal(own.ref_fleet.link_rates,
                              a.ref_fleet.link_rates)
    assert _hash(a.data.xs) == _hash(own.data.xs) != _hash(b.data.xs)
