"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload sec4_solo --seed 7 --seconds 20 \
        --trace 0

Everything about a cell is found by name from `BENCHMARK.json`: its
configuration in `chipbench/configs/<config>.json`, whose parts are
found by the names it gives them (`deploy.py`): its data builder in
`chipbench/datasets/<data.kind>.py`, its fleet in
`chipbench/fleets/<fleet.kind>.py` and its session reference in
`chipbench/reference/<reference>.py`; its traffic in
`chipbench/traffic/<traffic>.json` (which names its driver,
`chipbench/traffic/<driver>.py`); and each metric's reader in
`chipbench/metrics/<metric>.py` (or `<metric before its first dot>.py`).

The run refuses to start without a TPU, or with fewer chips than the cell
asks for.  It makes its inputs from `--seed`, warms up every shape the
traffic uses (set-up, `setup_s`), then runs the traffic for `--seconds`.
With `--trace 1` it traces `trace_seconds` of that window instead and
reports the per-layer metrics.  Once the window has closed it compares the
sampled answers with the float64 reference (`checks.py`), and prints the
numbers compared beside their limits on standard error and, as its last
line on standard output, one JSON object: correct, attempted, failed,
metrics, device, breakdown (traced runs) and checks.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# libtpu would otherwise log under a fixed path in /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
for _p in (HERE, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from deploy import load_module  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# the benchmark's host spans (`Ctx.span`) a traced run reads back
SPANS = ("window", "session", "plan", "run")


def load_json(*parts: str) -> Any:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_reader(name: str):
    for stem in (name, name.split(".")[0]):
        path = os.path.join(HERE, "metrics", stem + ".py")
        if os.path.exists(path):
            return load_module(path)
    raise FileNotFoundError(f"no reader for metric {name!r}")


def cell_metrics(bench: Dict[str, Any], cell: str, kind: str) -> List[dict]:
    """The metrics of `kind` ("end_to_end" or "per_layer") this cell
    reports."""
    e2e = {m["name"]: m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])}
    out = []
    for m in bench[kind]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


class CompileCounter:
    """Compiles (and persistent-cache loads) JAX reports."""

    def __init__(self):
        import jax

        self.count = 0

        def on(event, secs, **_):
            if event == COMPILE_EVENT:
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on)


@dataclasses.dataclass
class Ctx:
    """What a traffic driver and a metric reader see of the run."""

    system: Any
    traffic: Dict[str, Any]
    seed: int
    chips: int
    spans: List[tuple] = dataclasses.field(default_factory=list)
    calls: List[dict] = dataclasses.field(default_factory=list)
    window: tuple = (0.0, 0.0)
    setup_s: float = 0.0
    trace: Any = None
    peaks: Optional[Dict[str, float]] = None
    driver: Any = None
    keep: Any = None  # which calls' answers are compared (the driver's)
    # (name, key, rng, overrides, state, report) of those calls, as the
    # program returned them
    answers: List[Any] = dataclasses.field(default_factory=list)

    @contextlib.contextmanager
    def span(self, name: str, **meta):
        """A host span, kept in memory and written into the profiler's
        trace under `name`."""
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.spans.append((name, t0, time.perf_counter(), meta))

    def work(self, calls=None):
        return self.driver.work(self, self.calls if calls is None
                                else calls)


def chip_check(chips: int) -> Optional[str]:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        return f"JAX found no TPU (platform {devs[0].platform!r})"
    if len(devs) < chips:
        return f"the cell needs {chips} chips, JAX found {len(devs)}"
    return None


def run_loop(ctx: Ctx, seconds: float) -> None:
    """Closed loop: issue calls until `seconds` have passed; the window
    ends when the last call issued before then returns."""
    i, t0 = 0, time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        try:
            ctx.calls.append(ctx.driver.call(ctx, i))
        except Exception as e:  # a failed call counts, the loop goes on
            print(f"call {i} failed: {e!r}", file=sys.stderr)
            ctx.calls.append({"issued": t0, "done": time.perf_counter(),
                              "lanes": ctx.traffic.get("lanes", 1),
                              "epochs": 0, "failed": ctx.traffic.get(
                                  "lanes", 1), "sessions": []})
        i += 1
    ctx.window = (t0, time.perf_counter())


def traced_loop(ctx: Ctx, seconds: float, out_dir: str) -> None:
    import jax

    from trace_reduce import reduce_trace

    shutil.rmtree(out_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host spans are the benchmark's own
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("window"):
            run_loop(ctx, seconds)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    summary = reduce_trace(path, SPANS)
    summary.devices = summary.devices[:ctx.chips]
    ctx.trace = summary


def memory_peak(chips: int) -> int:
    import jax

    peaks = []
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, config: Optional[dict] = None,
             traffic: Optional[dict] = None,
             bench: Optional[dict] = None) -> Optional[dict]:
    """One run of a cell; returns the result object, or None where the
    machine cannot run it.  `config`, `traffic` and `bench` stand in for
    the files (the tests run cut-down cells on the CPU with
    `require_tpu=False`)."""
    bench = bench or load_json(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    if require_tpu:
        why = chip_check(cell["chips"])
        if why:
            print(f"refused: {why}", file=sys.stderr)
            return None
    import jax

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    compiles = CompileCounter()

    import checks
    from deploy import build

    cfg = config or load_json(HERE, "configs", cell["config"] + ".json")
    # the precision the configuration states for the program's products
    # that name none of their own
    jax.config.update("jax_default_matmul_precision",
                      cfg["matmul_precision"])
    traffic = traffic or load_json(HERE, "traffic",
                                   cell["traffic"] + ".json")
    kind = jax.devices()[0].device_kind
    peaks = load_json(HERE, "peaks.json").get(kind)
    if require_tpu and peaks is None:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    ctx = Ctx(system=build(cfg, seed, traffic.get("fleet_seed")),
              traffic=traffic, seed=seed, chips=cell["chips"], peaks=peaks,
              driver=load_module(os.path.join(
                  HERE, "traffic", traffic["driver"] + ".py")))
    ctx.driver.warm(ctx)
    ctx.setup_s = time.perf_counter() - T0
    c0 = compiles.count
    ctx.spans.clear()
    if trace:
        traced_loop(ctx, min(seconds, traffic["trace_seconds"]),
                    os.path.join(HERE, "out", "trace", workload))
    else:
        run_loop(ctx, seconds)
    in_window = compiles.count - c0
    lanes = sum(c["lanes"] for c in ctx.calls)
    failed = sum(c["failed"] for c in ctx.calls)
    print(f"window: {len(ctx.calls)} calls, {lanes} sessions, "
          f"{ctx.window[1] - ctx.window[0]:.3f} s, {in_window} compiles "
          f"in the window", file=sys.stderr)
    peak = memory_peak(ctx.chips)

    metrics: Dict[str, dict] = {}
    for m in cell_metrics(bench, workload,
                          "per_layer" if trace else "end_to_end"):
        value = find_reader(m["name"]).read(ctx, m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    t_ref = time.perf_counter()
    answers = [checks.program_answer(*kept) for kept in ctx.answers]
    ctx.answers = []
    numbers = [checks.compare(ctx.system, a) for a in answers]
    verdict, ok = checks.verdict(cfg, numbers)
    ok = ok and failed == 0
    print(f"answers compared: {len(numbers)} in "
          f"{time.perf_counter() - t_ref:.1f} s", file=sys.stderr)
    device = {"platform": jax.devices()[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": bool(ok), "attempted": lanes, "failed": failed,
              "metrics": metrics, "device": device}
    if ctx.trace is not None:
        tr = ctx.trace
        busy = [tr.busy_ns(d) for d in tr.devices] or [0.0]
        device["busy_s"] = sum(busy) / len(busy) * 1e-9
        device["window_s"] = tr.window_ns * 1e-9
        result["breakdown"] = {"device_ops": tr.top_ops(),
                               "idle_gaps": tr.idle_gaps()}
    for k, v in verdict.items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    result["checks"] = verdict
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    if result is None:
        return 3
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
