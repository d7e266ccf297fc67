"""recompiles.<cells> (count, program counter): compiles, and loads from
the persistent compilation cache, inside the window: the program's
``jax.compile`` records under a window span (an LDA sweep, or an engine
admission or step).  Every shape is warmed up in set-up, so this reads 0.
One reader for every cell; each ``recompiles.*`` entry of
``BENCHMARK.json`` says which end-to-end metric it moves."""

from bench import program_spans


def read(rec):
    spans = program_spans.select(rec)
    if spans is None:
        return None
    return float(sum(s.name == "jax.compile" for s in spans))
