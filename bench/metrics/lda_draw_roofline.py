"""lda_draw_roofline (%, device trace): the z-draw's share of its roofline.
The public ``gibbs.draw_z`` program, run once in the traced window on the
cell's state inside a ``bench.draw_z`` span; the least time of
``work.lda_draw_work`` over the device time inside that span.  Moves
lda_tokens_per_s."""

from bench import trace_reduce, work


def read(rec):
    tr = rec.get("trace")
    if tr is None:
        return None
    lo, hi = rec["window_ns"]
    t = trace_reduce.device_time_in(tr, trace_reduce.span_intervals(tr, "bench.draw_z", lo, hi))
    if t <= 0:
        return None
    c = rec["config"]
    flops, nbytes = work.lda_draw_work(rec["tokens"], c["M"], c["V"], c["K"])
    return 100.0 * work.least_time_s(flops, nbytes, rec["peaks"]) / t
