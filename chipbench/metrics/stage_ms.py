"""stage_ms.<cell kind>: the program's operand staging per `repro.run`
span: summed `repro.stage` spans (`device_state`, bucket keys, stacking
and host-to-device copies) over the number of `repro.run` spans, in the
traced window (ms, host spans)."""
import program_spans


def read(ctx, name):
    return program_spans.per_run_ms(ctx, "repro.stage")
