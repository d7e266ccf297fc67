"""The serving control of the DeepSeek-V3 configurations: the reference's
forward with every weight matrix (the router's too) rounded per output
channel to fp8 (e4m3), the step below the configuration's bfloat16 that
would tempt a later change.  It need not decode: at each position of the
prompts and tokens the program served, the control's own choice (its first
token where the request was greedy, its top-k where it sampled) is judged
by the float32 reference in the program's place.  ``patch()`` turns that on
in ``bench/refs/deepseek_v3.py``."""

from __future__ import annotations

import contextlib

from bench.harness import load_module


@contextlib.contextmanager
def patch():
    ref = load_module("refs", "deepseek_v3")
    ref.CONTROL = True
    try:
        yield
    finally:
        ref.CONTROL = False
