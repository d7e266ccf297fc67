"""admit_ms.<cells> (ms, program span): the median of the engine's
``engine.admit`` spans in the window: one request's prefill, its insert
into a slot and the seed-pair readback that waits for both, so the synced
time of one admission.  Moves ttft_p85_ms."""

from bench import program_spans


def read(rec):
    return program_spans.median(
        program_spans.durations_ms(program_spans.select(rec) or [], "engine.admit"))
