"""session_p95_ms: 95th percentile, over every session completed in the
window, of issue to `TraceReport` in hand, host clock (ms).  A failed
session counts as infinitely late."""
import sys

import numpy as np


def read(ctx, name):
    lat = [(c["done"] - c["issued"]) * 1e3 if not c["failed"] else np.inf
           for c in ctx.calls]
    print(f"{name}: {len(lat)} sessions, median "
          f"{float(np.median(lat))!r} ms", file=sys.stderr)
    return float(np.percentile(lat, 95))
