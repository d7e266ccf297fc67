"""The program's own spans (``repro.obs``), selected for one run's window.

The readers of the ``program_span`` and ``program_counter`` metrics run in
the runner's process after ``runner.run`` returns, so the program's span
ring still holds the run.  Which spans belong to the window:

  serving  every ``engine.*`` span opened at or after the window's start
           (``rec["window"][0]``, perf_counter seconds), through the drain:
           the steps ``decode_step_ms`` reads;
  LDA      the last ``rec["sweeps"]`` ``lda.sweep`` spans: set-up runs the
           checked sweeps first, the window exactly ``rec["sweeps"]`` more;

and, in both, every span whose chain of parents reaches one of those (a
``jax.compile`` under a window step is a recompile).  Where the program
keeps no such record (a program older than ``repro.obs``) ``select``
returns None, and so do the readers.
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional


def ring() -> Optional[list]:
    """The program's span ring, oldest first; None without ``repro.obs``."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.spans()


def select(rec: dict) -> Optional[list]:
    """The window's spans and their descendants, oldest first; None where
    the program records no spans or none lies in the window."""
    spans = ring()
    if spans is None:
        return None
    if "sweeps" in rec:
        n = int(rec["sweeps"])
        sweeps = sorted((s for s in spans if s.name == "lda.sweep"),
                        key=lambda s: s.start_ns)
        roots = {s.id for s in sweeps[max(0, len(sweeps) - n):]} if n else set()
    else:
        lo = rec["window"][0] * 1e9
        roots = {s.id for s in spans if s.name.startswith("engine.") and s.start_ns >= lo}
    if not roots:
        return None
    parent = {s.id: s.parent for s in spans}

    def inside(i) -> bool:
        while i is not None:
            if i in roots:
                return True
            i = parent.get(i)
        return False

    return [s for s in spans if inside(s.id)]


def durations_ms(spans: list, name: str) -> List[float]:
    return [(s.end_ns - s.start_ns) * 1e-6 for s in spans if s.name == name]


def per_parent_ms(spans: list, names) -> Dict[int, float]:
    """The summed time of the named spans under each parent."""
    out: Dict[int, float] = {}
    for s in spans:
        if s.name in names:
            out[s.parent] = out.get(s.parent, 0.0) + (s.end_ns - s.start_ns) * 1e-6
    return out


def median(xs) -> Optional[float]:
    xs = list(xs)
    return float(statistics.median(xs)) if xs else None
