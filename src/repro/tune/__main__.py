"""CLI: tune Pallas tiles and persist them.

    python -m repro.tune                         # tune the CI shape set
    python -m repro.tune --family encode --shape 2048x512x512
    python -m repro.tune --ci-defaults           # regenerate the committed
                                                 # src/repro/tune/defaults.json

Winners land in the user cache (`$REPRO_TUNE_CACHE_DIR/tiles.json`,
which must be set); `--ci-defaults` writes the in-repo fallback instead
(commit the result).  `block="auto"` consults
both — this CLI is the ONLY thing that ever autotunes.
"""
from __future__ import annotations

import argparse
import sys

from .cache import TileCache, defaults_path
from .families import CI_SHAPES, FAMILIES
from .tuner import DEFAULT_SLACK, tune_shapes


def _parse_shape(text: str) -> tuple:
    return tuple(int(v) for v in text.replace(",", "x").split("x"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.tune")
    ap.add_argument("--family", choices=sorted(FAMILIES), default=None,
                    help="tune one family (default: all)")
    ap.add_argument("--shape", default=None,
                    help="one shape, e.g. 2048x512x512 (requires --family)")
    ap.add_argument("--slack", type=float, default=DEFAULT_SLACK,
                    help="roofline pruning slack factor")
    ap.add_argument("--iters", type=int, default=5,
                    help="timed calls per surviving candidate")
    ap.add_argument("--ci-defaults", action="store_true",
                    help="tune the CI shape set into the committed "
                         "src/repro/tune/defaults.json")
    args = ap.parse_args(argv)

    if args.shape and not args.family:
        ap.error("--shape requires --family")
    if args.shape:
        shapes = {args.family: [_parse_shape(args.shape)]}
    elif args.family:
        shapes = {args.family: CI_SHAPES[args.family]}
    else:
        shapes = None  # the full CI set

    cache = TileCache(defaults_path()) if args.ci_defaults else None
    results = tune_shapes(shapes, cache=cache, slack=args.slack,
                          iters=args.iters, verbose=True)
    target = cache.path if cache else "user cache"
    print(f"{len(results)} entries written to {target}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
