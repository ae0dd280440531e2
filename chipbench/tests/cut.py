"""Cut-down cells for the CPU tests: the configuration files' structure
at sizes a test run holds, with the limits of the real configuration."""
import copy
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# n clients x ell rows of width d; c parity rows; epochs
N, ELL, D, C, EPOCHS = 6, 40, 16, 48, 60
# the CodedFedL cut: Fourier features and classes
D_FEAT, CLASSES = 24, 3


def config(name: str = "cfl_sec4") -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        cfg = json.load(f)
    cfg["data"].update(n=N, ell=ELL, d=D)
    if "head" in cfg["data"]:
        cfg["data"]["n_classes"] = CLASSES
        cfg["data"]["head"]["d_feat"] = D_FEAT
        cfg["fleet"]["d"] = D_FEAT
    for spec in cfg["strategies"].values():
        if "fixed_c" in spec:
            spec["fixed_c"] = C
    cfg["epochs"] = EPOCHS
    return cfg


def traffic(name: str) -> dict:
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        t = json.load(f)
    t["warm_rounds"] = 1
    if "fixed_c" in t:
        t["fixed_c"] = [C - 8, C, C + 8, C + 16]
        t["lanes"] = 4
        t["check"] = {"blocks": 4, "within_first": 1}
    else:
        t["check"] = dict(t["check"], within_first=4)
    return t


def bench(workload: str, traffic_name: str) -> dict:
    """A benchmark with the one cell, reporting only `setup_s`."""
    return {"workloads": [{"name": workload, "config": "cut",
                           "traffic": traffic_name, "chips": 1}],
            "end_to_end": [{"name": "setup_s", "unit": "s"}],
            "per_layer": []}


def run(workload: str, traffic_name: str, seed: int, cfg=None):
    """One run of a cut cell of `traffic_name` over `cfg` (default: the
    cut §IV configuration)."""
    import run as harness

    return harness.run_cell(workload, seed, 0.5, False, require_tpu=False,
                            config=copy.deepcopy(cfg or config()),
                            traffic=traffic(traffic_name),
                            bench=bench(workload, traffic_name))
