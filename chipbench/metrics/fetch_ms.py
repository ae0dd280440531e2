"""fetch_ms.<cell kind>: the host waiting for the epoch engine per
`repro.run` span: summed `repro.fetch` spans (the host copy of the
engine's outputs, which waits for the device) over the number of
`repro.run` spans, in the traced window (ms, host spans)."""
import program_spans


def read(ctx, name):
    return program_spans.per_run_ms(ctx, "repro.fetch")
