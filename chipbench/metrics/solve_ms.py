"""solve_ms.<cell kind>: mean `repro.solve` span (the batched redundancy
solve of `plan/solver`, which only coded strategies call), in the traced
window (ms, host spans)."""
import program_spans


def read(ctx, name):
    found = program_spans.spans(ctx)
    solves = program_spans.named(found or [], "repro.solve")
    if not solves:
        return None
    return sum(s.ns for s in solves) / len(solves) * 1e-6
