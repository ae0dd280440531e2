"""Build a configuration's deployment from its file and the run's seed.

    system = build(config_dict, seed[, fleet_seed])
    system.data, system.sizes        # what the program is given
    system.fleet, system.ref_fleet   # the fleet, as the program and the
                                     # reference build it
    system.reference                 # the configuration's reference module
    strategy(system, "cfl", key=k)   # one of the configuration's strategies

Each part is found by name, so a new deployment brings files of its own
and edits none:

    chipbench/datasets/<data.kind>.py   build(spec, seed) -> (TrainData,
                                        rows per client)
    chipbench/fleets/<fleet.kind>.py    build(spec, data_spec, seed) ->
                                        (program fleet, reference fleet)
    chipbench/reference/<reference>.py  answer, root and work of a session
                                        (see `checks.py`)

Inputs are made from `--seed` alone: the data on the device in one jitted
call, the fleet on the host, each from its own stream of the seed.  A
traffic mix may fix the fleet's draw (`"fleet_seed"`), so that every seed
plans the same fleet.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import os
from typing import Any, Dict, Optional

import jax
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def derive(seed: int, *tags: int) -> int:
    """A 31-bit integer drawn from (seed, *tags); seeds may be any size."""
    ss = np.random.SeedSequence([int(seed) % 2 ** 64, *tags])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


def load_module(path: str):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + os.path.basename(path)[:-3].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def part(folder: str, name: str):
    """The module `chipbench/<folder>/<name>.py`, loaded once a process."""
    return load_module(os.path.join(HERE, folder, name + ".py"))


@dataclasses.dataclass
class System:
    cfg: Dict[str, Any]
    seed: int
    data: Any
    sizes: np.ndarray  # (n,) rows per client
    fleet: Any
    ref_fleet: Any
    reference: Any  # the module `reference/<cfg["reference"]>.py`
    rff_key: int = 0
    # what the reference computes once per run (host data, features,
    # deadlines), kept for every answer it compares
    cache: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def spec(self, name: str, overrides: Dict[str, Any]) -> Dict[str, Any]:
        """Strategy `name` of the configuration, with the traffic's
        overrides."""
        return dict(self.cfg["strategies"][name], **overrides)


def build(cfg: Dict[str, Any], seed: int,
          fleet_seed: Optional[int] = None) -> System:
    """The deployment of `cfg` under `seed`; the fleet is drawn from
    `fleet_seed` where a traffic mix fixes one (the same fleet under
    every seed), else from `seed`."""
    data, sizes = part("datasets", cfg["data"]["kind"]).build(cfg["data"],
                                                              seed)
    jax.block_until_ready(data.xs)
    fleet, ref_fleet = part("fleets", cfg["fleet"]["kind"]).build(
        cfg["fleet"], cfg["data"],
        seed if fleet_seed is None else fleet_seed)
    return System(cfg=cfg, seed=seed, data=data, sizes=sizes, fleet=fleet,
                  ref_fleet=ref_fleet,
                  reference=part("reference", cfg["reference"]),
                  rff_key=derive(seed, 7))


def strategy(system: System, name: str, key: int, **overrides):
    """The configuration's strategy `name`, keyed by `key` where it draws
    generator matrices; a strategy on the data's feature head shares the
    run's feature map (`rff_key`, one per run, so the head stays valid)."""
    from repro.api import make_strategy

    spec = system.spec(name, overrides)
    kind = spec.pop("kind")
    if spec.pop("keyed", False):
        spec["key"] = jax.random.PRNGKey(key)
    if spec.pop("head", False):
        head = system.cfg["data"]["head"]
        spec.update(d_feat=head["d_feat"], rff_gamma=head["rff_gamma"],
                    rff_key=jax.random.PRNGKey(system.rff_key))
    return make_strategy(kind, **spec)
