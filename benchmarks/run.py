"""Benchmark harness: one entry per paper figure + kernels + roofline.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--only fig2,fig3,...]

Prints ``name,us_per_call,derived`` CSV lines (stdout); paper-claim
comparisons live in EXPERIMENTS.md.
"""
from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced epoch counts (CI-speed)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: fig2,fig3,fig4,fig5,"
                         "schemes,nonlinear,privacy,ablation,noniid,serve,"
                         "fleet,kernels,epoch,roofline")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    def want(name):
        return only is None or name in only

    t0 = time.time()
    print("name,us_per_call,derived")

    if want("fig2"):
        from . import fig2_convergence
        fig2_convergence.main(epochs=400 if args.fast else 1200)
    if want("fig3"):
        from . import fig3_histogram
        fig3_histogram.main(draws=4000 if args.fast else 20000)
    if want("fig4"):
        from . import fig4_coding_gain
        fig4_coding_gain.main(epochs=500 if args.fast else 1400)
    if want("fig5"):
        from . import fig5_comm_load
        fig5_comm_load.main(epochs=600 if args.fast else 1600)
    if want("schemes"):
        from . import fig_schemes
        # 600 epochs in both modes: the monotone-convergence gates need the
        # slow-deadline (low-delta) runs to actually reach the target
        fig_schemes.main(epochs=600)
    if want("nonlinear"):
        from . import fig_nonlinear
        fig_nonlinear.main(epochs=300 if args.fast else 600)
    if want("privacy"):
        from . import fig_privacy
        fig_privacy.main(epochs=200 if args.fast else 400)
    if want("noniid"):
        from . import noniid
        noniid.main(epochs=600 if args.fast else 1200)
    if want("ablation"):
        from . import ablation_baselines
        ablation_baselines.main(epochs=600 if args.fast else 1000)
    if want("serve"):
        from . import perf_serve
        perf_serve.main(epochs=240 if args.fast else 400)
    if want("fleet"):
        from . import perf_fleet
        perf_fleet.main(n=perf_fleet.FLEET_N // 10 if args.fast
                        else perf_fleet.FLEET_N)
    if want("kernels"):
        from . import kernels
        kernels.main()
    if want("epoch"):
        from . import perf_session
        # fused-vs-reference round-gradient path (gated in CI epoch-smoke)
        perf_session.main(epochs=300, smoke=True, epoch=True)
    if want("roofline"):
        from . import roofline_table
        # always prints the coded-kernel attainment section; the dry-run
        # mesh section self-skips when dryrun_results.json is absent
        roofline_table.main()

    print(f"total,{(time.time() - t0) * 1e6:.0f},benchmark suite wall time")
    return 0


if __name__ == "__main__":
    sys.exit(main())
