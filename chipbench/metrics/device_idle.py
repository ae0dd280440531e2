"""device_idle: 1 - (union of device-op intervals / traced window), as a
percentage, averaged over the cell's chips (device trace)."""
import sys


def read(ctx, name):
    tr = ctx.trace
    if tr is None or not tr.devices:
        return None
    idle = [100.0 * (1.0 - tr.busy_ns(d) / tr.window_ns)
            for d in tr.devices]
    for d, v in zip(tr.devices, idle):
        print(f"{name} device {d.index}: {v!r} %", file=sys.stderr)
    return sum(idle) / len(idle)
