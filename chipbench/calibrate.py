"""Read the control's numbers for a cell: the upper readings of its limits.

    python3 chipbench/calibrate.py --workload sec4_solo \
        --seeds 11 12 13 [--lanes 2] [--out chiprun_out/control.jsonl]

For each seed it builds the cell's inputs as a run does, takes the
sessions whose answers a run of that seed would compare, computes each
with the reference one step below the stated precision (`reference.HIGH`:
float32 with products in three bfloat16 passes) in the program's place,
and compares that with the float64 reference exactly as `checks.compare`
compares the program.  `--lanes` caps the sessions read per seed.  It
prints one JSON line per seed and, last, the least value of each number
over the seeds.  The benchmark's own runs never run this; the lower
readings are the worst values the program's runs print.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import checks  # noqa: E402
import run  # noqa: E402
from deploy import build  # noqa: E402
from reference import HIGH  # noqa: E402


def control_numbers(workload: str, seed: int, lanes: int) -> list:
    bench = run.load_json(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    cfg = run.load_json(HERE, "configs", cell["config"] + ".json")
    traffic = run.load_json(HERE, "traffic", cell["traffic"] + ".json")
    driver = run.load_module(os.path.join(HERE, "traffic",
                                          traffic["driver"] + ".py"))
    ctx = run.Ctx(system=build(cfg, seed, traffic.get("fleet_seed")),
                  traffic=traffic, seed=seed, chips=cell["chips"])
    out = []
    for name, key, rng, over in driver.compared(ctx)[:lanes]:
        ans = ctx.system.reference.answer(ctx.system, name, key, rng, over,
                                          HIGH)
        out.append({"strategy": name, **over,
                    **checks.compare(ctx.system, ans)})
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--lanes", type=int, default=99)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    least: dict = {}
    lines = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        nums = control_numbers(args.workload, seed, args.lanes)
        line = {"workload": args.workload, "seed": seed, "control": nums,
                "seconds": time.perf_counter() - t0}
        lines.append(line)
        print(json.dumps(line), flush=True)
        for n in nums:
            for k, v in n.items():
                if isinstance(v, float):
                    least[k] = min(least.get(k, float("inf")), v)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "a") as f:
            for line in lines:
                f.write(json.dumps(line) + "\n")
    print(json.dumps({"workload": args.workload, "least": least}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
