"""idle_share.<cells> (%, device trace): the share of the traced window in
which no operation ran on the chip, 1 - busy / window.  One reader for every
cell kind; each ``idle_share.*`` entry of ``BENCHMARK.json`` says which
end-to-end metric it moves."""

from bench import trace_reduce


def read(rec):
    tr = rec.get("trace")
    if tr is None:
        return None
    busy, win = trace_reduce.busy_share(tr, *rec["window_ns"])
    return 100.0 * (1.0 - busy / win) if win > 0 else None
