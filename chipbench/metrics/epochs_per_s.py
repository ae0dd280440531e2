"""epochs_per_s: lane-epochs of training completed in the window, over the
whole window, host clock.  The window ends when the last call issued
before `--seconds` ran out returns."""


def read(ctx, name):
    epochs = sum(c["lanes"] * c["epochs"] for c in ctx.calls
                 if not c["failed"])
    return epochs / (ctx.window[1] - ctx.window[0])
