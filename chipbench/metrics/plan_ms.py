"""plan_ms.<cell kind>: mean host span of the benchmark's `plan` span per
call that encodes parity (`Session.plan` or `plan_sweep`, ended by
`block_until_ready` on the state's arrays), in the traced window (ms)."""


def read(ctx, name):
    if ctx.trace is None:
        return None
    spans = [t1 - t0 for n, t0, t1, meta in ctx.spans
             if n == "plan" and meta.get("coded")]
    if not spans:
        return None
    return sum(spans) / len(spans) * 1e3
