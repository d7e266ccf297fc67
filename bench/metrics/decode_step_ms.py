"""decode_step_ms.<cells> (ms, program span): the median of the engine's own
step_times, each a host wall time that ends in the step's host sync.  One
reader for every serving cell; each ``decode_step_ms.*`` entry of
``BENCHMARK.json`` says which end-to-end metric it moves."""

import numpy as np


def read(rec):
    xs = rec.get("step_ms")
    return float(np.median(xs)) if xs else None
