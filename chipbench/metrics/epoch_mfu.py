"""epoch_mfu: the algorithm's operations for the epochs completed in the
traced window (`count.py`, a lower bound), over traced seconds x chips x
the chip's bf16 peak (`peaks.json`), as a percentage.  The epochs run in
float32, for which no peak is published, so the bf16 peak is the bound."""


def read(ctx, name):
    tr = ctx.trace
    if tr is None or not tr.devices or ctx.peaks is None:
        return None
    work = ctx.work()
    seconds = tr.window_ns * 1e-9
    return 100.0 * work.flops / (
        seconds * ctx.chips * ctx.peaks["bf16_flops_per_s"])
