"""decode_draw_roofline (%, device trace): the truncated vocabulary draw's
share of its roofline.  Its kernels are found by name in the traced window's
ops (``KERNELS``: the HLO name of a Pallas call is that of the function
that issues it, here the fused truncated draw's two passes); each decode
step's draw needs its (slots, vocab) float32
weights read once and one index written per slot
(``work.vocab_draw_bytes``), bytes bound.  Moves output_tokens_per_s."""

from bench import trace_reduce, work

KERNELS = ("%butterfly_sample_truncated_pallas",)
DECODE = ("jit_step",)


def read(rec):
    tr = rec.get("trace")
    if tr is None:
        return None
    lo, hi = rec["window_ns"]
    t, _ = trace_reduce.op_time(tr, KERNELS, lo, hi)
    _, steps = trace_reduce.module_time(tr, DECODE, lo, hi)
    if t <= 0 or steps == 0:
        return None
    c = rec["config"]
    nbytes = steps * work.vocab_draw_bytes(rec["engine"]["max_slots"], c["vocab_size"])
    return 100.0 * work.least_time_s(0, nbytes, rec["peaks"]) / t
