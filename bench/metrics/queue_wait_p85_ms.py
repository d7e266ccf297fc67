"""queue_wait_p85_ms.<cells> (ms, program span): the 85th percentile of the
engine's ``engine.queue`` spans in the window, each from a request's
arrival (its submit) to the start of its admission.  Admission happens only
between decode steps, so a request waits up to one step, plus the
admissions ahead of it.  Moves ttft_p85_ms."""

import numpy as np

from bench import program_spans


def read(rec):
    xs = program_spans.durations_ms(program_spans.select(rec) or [], "engine.queue")
    return float(np.percentile(np.asarray(xs), 85)) if xs else None
