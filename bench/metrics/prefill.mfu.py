"""prefill.mfu (%, device trace): prefill's share of the chip's bf16 peak.
The operations that the unpadded prompts prefilled in the traced window
require (``work.qwen3_prefill_flops``, one prompt at a time as the engine
prefills them) over the device time of the engine's prefill programs there,
at 197 TFLOP/s.  Power-of-two padding shows here as lost share.

The engine's prefill is a jitted lambda, so its programs are found by the
jit name ``jit__lambda``; the engine's seed-pair lambda shares that name and
adds microseconds per request.  Moves ttft_p90_ms."""

from bench import trace_reduce, work

PROGRAMS = ("jit__lambda",)


def read(rec):
    tr = rec.get("trace")
    if tr is None or len(rec.get("trace_t", ())) != 2:
        return None
    lo, hi = rec["window_ns"]
    t, _ = trace_reduce.module_time(tr, PROGRAMS, lo, hi)
    a, b = rec["trace_t"]
    flops = sum(work.qwen3_prefill_flops(rec["config"], n)
                for when, n in rec.get("prefill_tokens", ()) if a <= when < b)
    if t <= 0 or flops == 0:
        return None
    return 100.0 * flops / rec["peaks"]["bf16_flops_per_s"] / t
