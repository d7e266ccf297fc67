"""itl_p99_ms (ms, host clock): the 99th percentile of the gaps between
consecutive output tokens, over all requests due in the window."""

import numpy as np


def read(rec):
    xs = rec.get("itl_s")
    return float(np.percentile(np.asarray(xs), 99)) * 1e3 if xs else None
