"""step_host_ms.<cells> (ms, program span): the host's own time in a decode
step, the median over the window's steps of ``engine.step.dispatch`` (the
slot arrays to the device and the step call) plus ``engine.step.walk``
(the per-slot loop and the finishes).  The device waits on this between
steps.  One reader for every serving cell; each ``step_host_ms.*`` entry of
``BENCHMARK.json`` says which end-to-end metric it moves."""

from bench import program_spans

HOST = ("engine.step.dispatch", "engine.step.walk")


def read(rec):
    per_step = program_spans.per_parent_ms(program_spans.select(rec) or [], HOST)
    return program_spans.median(per_step.values())
