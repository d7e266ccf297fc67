"""ttft_p85_ms (ms, host clock): the 85th percentile, over every request due
in the window, of the time from its due time to its first token as the
benchmark saw it arrive.  A refused request, or one unfinished at the drain
limit, counts as infinitely late.  At the chat cell's rate the window holds
41 requests, so six lie beyond the 85th percentile."""

import numpy as np


def read(rec):
    xs = rec.get("ttft_s")
    return float(np.percentile(np.asarray(xs), 85)) * 1e3 if xs else None
