"""round_grad_roofline: the least time the chip needs for the masked round
gradient's operations and bytes (`count.py`, at the peaks of
`peaks.json`), over the summed device time of the `round_grad` Pallas
kernels in the traced window, as a percentage (device trace).  The kernels
are found by the names of their Pallas calls.  Where every session's rows
fit in the chip's VMEM they may stay there across epochs, so the least
time is then the compute time alone (`count.least_seconds`)."""
import sys

import count

# the Pallas calls of kernels/round_grad, named after their entry points
KERNELS = r"/%(masked|coded|tier_masked)_round_gradient\b"


def read(ctx, name):
    tr = ctx.trace
    if tr is None or not tr.devices or ctx.peaks is None:
        return None
    kernel_s = sum(tr.op_time(KERNELS)) * 1e-9
    if kernel_s <= 0:
        return None
    work = ctx.work()
    least, bound = count.least_seconds(work.sys_flops, work.sys_bytes,
                                       ctx.peaks, work.resident)
    print(f"{name}: kernel {kernel_s!r} s, least {least!r} s, bound by "
          f"{bound}", file=sys.stderr)
    return 100.0 * least / kernel_s
