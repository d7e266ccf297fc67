"""output_tokens_per_s (tokens/s, host clock): output tokens that reached
the benchmark within the window, over the window."""


def read(rec):
    if "output_tokens" not in rec:
        return None
    t0, end = rec["window"]
    return rec["output_tokens"] / (end - t0)
