"""setup_s (s, host clock): from the start of the process to the start of
the measured window: imports, data, weights, warm-up and any compile."""


def read(rec):
    return rec["setup_s"]
