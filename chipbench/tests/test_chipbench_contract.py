"""`BENCHMARK.json` is whole: every name it uses resolves to a file of the
benchmark, and every per-layer metric's `moves` is reported by each cell
it lists; every configuration file names parts that exist and expose the
interface the harness calls."""
import glob
import inspect
import json
import os
import re

import pytest

import deploy
import run

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_and_units(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    for entry in metrics + bench["workloads"] + bench["configs"]:
        assert NAME.match(entry["name"]), entry["name"]
    for m in metrics:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


def test_every_name_resolves_to_a_file(bench):
    configs = {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    used = set()
    for w in bench["workloads"]:
        assert w["config"] in configs
        used.add(w["config"])
        traffic = run.load_json(BENCH_DIR, "traffic", w["traffic"] + ".json")
        assert os.path.exists(os.path.join(BENCH_DIR, "traffic",
                                           traffic["driver"] + ".py"))
        assert w["chips"] in (1, 4)
    assert used == configs
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert run.find_reader(m["name"]) is not None


def test_each_moves_is_reported_where_listed(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        target = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in target.get("workloads", cells), (m["name"], cell)


def test_every_cell_reports_enough(bench):
    for w in bench["workloads"]:
        e2e = {m["name"] for m in run.cell_metrics(bench, w["name"],
                                                   "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert run.cell_metrics(bench, w["name"], "per_layer"), w["name"]


def test_bounds(bench):
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m["name"]
    setup = next(m for m in bench["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] == 0.25


# (folder, the configuration's name for it, function: its parameters)
PARTS = [
    ("datasets", lambda cfg: cfg["data"]["kind"],
     {"build": ["spec", "seed"]}),
    ("fleets", lambda cfg: cfg["fleet"]["kind"],
     {"build": ["spec", "data_spec", "seed"]}),
    ("reference", lambda cfg: cfg["reference"],
     {"answer": ["system", "name", "key", "rng", "overrides", "ar",
                 "t_star"],
      "root": ["system", "spec"],
      "work": ["system", "name", "rng", "plan"]}),
]


@pytest.mark.parametrize("folder, name_of, interface", PARTS,
                         ids=[p[0] for p in PARTS])
@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(BENCH_DIR, "configs", "*.json"))), ids=os.path.basename)
def test_every_configuration_names_its_parts(path, folder, name_of,
                                             interface):
    with open(path) as f:
        mod = deploy.part(folder, name_of(json.load(f)))
    for fn, params in interface.items():
        assert list(inspect.signature(getattr(mod, fn)).parameters) \
            == params, (folder, fn)
