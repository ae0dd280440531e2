"""run_host_ms.<cell kind>: mean, per `run` span (`Session.run` or
`run_sweep`), of the span's length minus the time inside it in which the
busiest device ran an op: the host's share of the call (ms, device trace
and host spans on the trace's clock)."""


def read(ctx, name):
    tr = ctx.trace
    if tr is None or not tr.spans.get("run") or not tr.devices:
        return None
    host = [(b - a) - max(tr.busy_ns(d, a, b) for d in tr.devices)
            for a, b in tr.spans["run"]]
    return sum(host) / len(host) * 1e-6
