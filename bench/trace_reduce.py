"""Reduce a profiler trace to the numbers the benchmark reports.

``load(path)`` reads an ``.xplane.pb`` into plain tuples; everything after
works on those, so the tests can feed it a synthetic or recorded trace
without a chip.  Importing this module loads nothing of the accelerator's
runtime.

Conventions taken from a v5e trace: each chip is a plane named
``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per executed HLO
instruction (a ``while`` encloses the ops of its body), its ``XLA Modules``
line one event per program run, named ``<jit name>(<fingerprint>)``.  Host
threads live on ``/host:CPU``; a ``jax.profiler.TraceAnnotation`` is an event
on the thread that opened it.  All start times share one clock.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# (name, start_ns, duration_ns)
Event = Tuple[str, float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass
class Trace:
    """Per-chip op and module events, and host spans, all in nanoseconds."""

    ops: Dict[str, List[Event]]       # chip plane name -> XLA Ops events
    modules: Dict[str, List[Event]]   # chip plane name -> XLA Modules events
    host: List[Event]                 # host spans with a given prefix


def load(path: str, host_prefix: str = "bench.") -> Trace:
    """Read an ``.xplane.pb`` written by ``jax.profiler``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                           for e in line.events]
                    (ops if line.name == OPS_LINE else modules)[plane.name] = evs
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events if e.name.startswith(host_prefix))
    return Trace(ops=ops, modules=modules, host=host)


# -- interval arithmetic ------------------------------------------------------


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Tuple[float, float]], lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def total(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float):
    """Idle (start, end) intervals of [lo, hi] not covered by ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [g for g in out if g[1] > g[0]]


# -- reductions ---------------------------------------------------------------


def window(tr: Trace, span: str) -> Tuple[float, float]:
    """(start, end) of the host span that bounds the traced window."""
    for name, s, d in tr.host:
        if name == span:
            return s, s + d
    raise KeyError(f"no host span {span!r} in the trace")


def busy_intervals(tr: Trace, plane: str, lo: float, hi: float):
    return clip(union((s, s + d) for _, s, d in tr.ops.get(plane, ())), lo, hi)


def busy_share(tr: Trace, lo: float, hi: float) -> Tuple[float, float]:
    """(busy seconds averaged over the chips, window seconds)."""
    planes = sorted(tr.ops)
    if not planes or hi <= lo:
        return 0.0, (hi - lo) * 1e-9
    busy = sum(total(busy_intervals(tr, p, lo, hi)) for p in planes) / len(planes)
    return busy * 1e-9, (hi - lo) * 1e-9


def span_intervals(tr: Trace, name: str, lo: float = float("-inf"),
                   hi: float = float("inf")):
    return clip(union((s, s + d) for n, s, d in tr.host if n == name), lo, hi)


def device_time_in(tr: Trace, spans: Sequence[Tuple[float, float]]) -> float:
    """Device busy seconds inside the given host intervals, averaged over
    chips: the device time of work the host waited for inside them."""
    planes = sorted(tr.ops)
    if not planes:
        return 0.0
    t = 0.0
    for p in planes:
        busy = union((s, s + d) for _, s, d in tr.ops[p])
        for lo, hi in spans:
            t += total(clip(busy, lo, hi))
    return t * 1e-9 / len(planes)


def module_base(name: str) -> str:
    """``jit_step(123456)`` -> ``jit_step``."""
    return name.split("(", 1)[0]


def module_time(tr: Trace, names: Sequence[str], lo: float, hi: float) -> Tuple[float, int]:
    """(device seconds averaged over chips, runs) of the programs whose jit
    name is one of ``names``, counting runs that start in [lo, hi)."""
    planes = sorted(tr.modules)
    if not planes:
        return 0.0, 0
    t, n = 0.0, 0
    for p in planes:
        for name, s, d in tr.modules[p]:
            if module_base(name) in names and lo <= s < hi:
                t += d
                n += 1
    return t * 1e-9 / len(planes), n // len(planes)


def op_time(tr: Trace, substrings: Sequence[str], lo: float, hi: float) -> Tuple[float, int]:
    """(device seconds averaged over chips, count) of op events whose name
    (the HLO text, which carries a custom call's kernel name) holds any of
    ``substrings``, starting in [lo, hi)."""
    planes = sorted(tr.ops)
    if not planes:
        return 0.0, 0
    t, n = 0.0, 0
    for p in planes:
        for name, s, d in tr.ops[p]:
            if lo <= s < hi and any(x in name for x in substrings):
                t += d
                n += 1
    return t * 1e-9 / len(planes), n // len(planes)


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Self time per op name: an op's duration less that of the ops nested
    in it (a ``while`` holds its body's ops)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out: Dict[str, float] = {}
    stack: List[Tuple[str, float]] = []  # (name, end)
    for name, s, d in evs:
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            parent = stack[-1][0]
            out[parent] = out.get(parent, 0.0) - d
        out[name] = out.get(name, 0.0) + d
        stack.append((name, s + d))
    return out


def short_op(name: str, width: int = 96) -> str:
    """An op's HLO text cut to its name, result type and opcode."""
    return " ".join(name.split())[:width]


def breakdown(tr: Trace, lo: float, hi: float, top: int = 10) -> dict:
    """The device ops that took most self time in [lo, hi), and the longest
    idle gaps of the first chip, each named by the innermost host span open
    at its middle."""
    planes = sorted(tr.ops)
    if not planes:
        return {"device_ops": [], "idle_gaps": []}
    p = planes[0]
    inside = [e for e in tr.ops[p] if lo <= e[1] < hi]
    st = self_times(inside)
    device_ops = sorted(st.items(), key=lambda kv: -kv[1])[:top]
    idle = gaps(busy_intervals(tr, p, lo, hi), lo, hi)
    named = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:top]:
        mid = 0.5 * (s + e)
        open_spans = [(d, n) for n, hs, d in tr.host if hs <= mid < hs + d]
        label = min(open_spans)[1] if open_spans else "no host span"
        named.append([label, (e - s) * 1e-9])
    return {
        "device_ops": [[short_op(n), t * 1e-9] for n, t in device_ops],
        "idle_gaps": named,
    }


def find_xplane(root: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    import glob
    import os

    files = glob.glob(os.path.join(root, "plugins", "profile", "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None
