"""Pallas TPU kernels for the perf-critical sampling hot spots.

Each kernel ships as ``kernel.py`` (pl.pallas_call + explicit BlockSpec VMEM
tiling), ``ops.py`` (jit'd public wrapper, interpret-mode fallback on CPU)
and ``ref.py`` (pure-jnp oracle used by the allclose test sweeps).
``runtime`` holds the one rule for where they compile natively and the
default block width and tiles they launch with.
"""
