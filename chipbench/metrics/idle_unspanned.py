"""idle_unspanned: share of the traced window in which the device ran no
op and no span of the program was open, as a percentage, averaged over
the cell's chips: the idle time the host spent outside the program
(caller, glue).  On standard error: each chip's share, the ten longest
idle gaps named by the innermost program span open at their middle, the
number of `repro.build` spans in the window (0 in a warm cell) and the
share of the `repro.run` spans their child spans cover."""
import sys

import program_spans


def read(ctx, name):
    tr = ctx.trace
    found = program_spans.spans(ctx)
    if tr is None or not tr.devices or not found:
        return None
    shares = program_spans.idle_unspanned(tr, found)
    for d, v in zip(tr.devices, shares):
        print(f"{name} device {d.index}: {v!r} %", file=sys.stderr)
    for span, secs in program_spans.idle_gaps(tr, found):
        print(f"{name} gap: {span} {secs!r} s", file=sys.stderr)
    builds = len(program_spans.named(found, "repro.build"))
    print(f"{name}: {builds} repro.build spans in the window",
          file=sys.stderr)
    print(f"{name}: repro.run covered by its child spans: "
          f"{program_spans.run_cover(found)!r} %", file=sys.stderr)
    return sum(shares) / len(shares)
