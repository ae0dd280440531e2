"""sample_us.<cell kind>: host time of the program's epoch sampling per
lane-epoch drawn: summed `repro.sample` span over the summed lanes x
epochs of its counts, in the traced window (us, host spans on the
trace's clock)."""
import program_spans


def read(ctx, name):
    found = program_spans.spans(ctx)
    if not found:
        return None
    sample = program_spans.named(found, "repro.sample")
    drawn = sum(s.counts.get("lanes", 0) * s.counts.get("epochs", 0)
                for s in sample)
    if not drawn:
        return None
    return sum(s.ns for s in sample) / drawn * 1e-3
