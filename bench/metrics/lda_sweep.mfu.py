"""lda_sweep.mfu (%, device trace): the whole sweep's share of the chip's
roofline.  The least time one sweep needs (``work.lda_sweep_work``: bytes
bound at K = 240) times the traced sweeps, over the device time spent inside
the benchmark's ``bench.sweep`` spans of the traced window.  Moves
lda_tokens_per_s."""

from bench import trace_reduce, work


def read(rec):
    tr = rec.get("trace")
    if tr is None or not rec.get("traced_sweeps"):
        return None
    lo, hi = rec["window_ns"]
    t = trace_reduce.device_time_in(tr, trace_reduce.span_intervals(tr, "bench.sweep", lo, hi))
    if t <= 0:
        return None
    c = rec["config"]
    flops, nbytes = work.lda_sweep_work(rec["tokens"], c["M"], c["V"], c["K"])
    return 100.0 * rec["traced_sweeps"] * work.least_time_s(flops, nbytes, rec["peaks"]) / t
