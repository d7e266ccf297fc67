"""decode_mla_moe.mfu (%, device trace): the whole decode step's share of
its roofline in an MLA + MoE cell.  For each step in the traced window, the
least time of ``work_mla_moe.decode_work`` (every held parameter read once,
the latent cache of every position the active sequences attend, bytes
bound), summed, over the device time of the engine's decode-step program
(jit name ``jit_step``) there.  Moves output_tokens_per_s."""

from bench import trace_reduce, work, work_mla_moe

PROGRAMS = ("jit_step",)


def read(rec):
    tr = rec.get("trace")
    if tr is None or len(rec.get("trace_t", ())) != 2:
        return None
    lo, hi = rec["window_ns"]
    t, n = trace_reduce.module_time(tr, PROGRAMS, lo, hi)
    a, b = rec["trace_t"]
    least = sum(work.least_time_s(*work_mla_moe.decode_work(rec["config"], tok, att),
                                  rec["peaks"])
                for when, tok, att in rec["steps"] if a <= when < b)
    if t <= 0 or least == 0:
        return None
    return 100.0 * least / t
