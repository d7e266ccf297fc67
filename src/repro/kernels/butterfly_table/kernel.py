"""Pallas kernel: the paper's butterfly-patterned partial-sums table (Alg. 8).

Grid is (G, nb): one W x W block of samples x categories per step, nb
(category blocks) innermost so a VMEM scratch row can carry the running
cross-block prefix (the paper's ``sum`` accumulator, lines 33-34 of Alg. 8).

The GPU ``shuffleXor(h, bit)`` becomes a lane permutation within the VMEM
tile (reshape -> flip -> reshape), and the four-element replacement
``[[a,b],[c,d]] -> [[a,d],[a+b,c+d]]`` is expressed with column-mask selects
— both vectorize on the VPU with no cross-tile traffic, which is the
TPU-native reading of "no transposed local writes" (DESIGN.md §2).

On real hardware one would fuse 128/W blocks along the lane axis per step;
the (W, W) BlockSpec here keeps the mapping to the paper 1:1 and validates
in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

def _rounds_inplace(m: jnp.ndarray, W: int) -> jnp.ndarray:
    """log2(W) butterfly rounds on a (W, W) tile (rows=samples, cols=cats).

    Fully unrolled with static row indices (the paper unrolls these loops
    manually for the CUDA compiler, §5; Pallas gets the same effect at
    trace time — no captured array constants allowed in kernels).
    """
    log2w = int(np.log2(W))
    col = jax.lax.broadcasted_iota(jnp.int32, (W,), 0)
    for b in range(log2w):
        bit = 1 << b
        has = (col & bit) != 0
        for d in range(bit - 1, W - 1, 2 * bit):
            a_d = m[d, :]
            a_db = m[d + bit, :]
            h = jnp.where(has, a_d, a_db)
            # shuffleXor(h, bit): flip lanes within each 2*bit lane group
            v = h.reshape(W // (2 * bit), 2, bit)[:, ::-1, :].reshape(W)
            new_d = jnp.where(has, a_db, a_d)
            new_db = new_d + v
            m = m.at[d, :].set(new_d).at[d + bit, :].set(new_db)
    return m


def _table_kernel(w_ref, out_ref, carry_ref, *, W: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    m = w_ref[...].astype(jnp.float32)
    m = _rounds_inplace(m, W)
    running = carry_ref[0, :] + m[W - 1, :]
    carry_ref[0, :] = running
    out_ref[...] = m.at[W - 1, :].set(running).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("W", "interpret"))
def butterfly_table_pallas(
    weights: jnp.ndarray, W: int = 32, interpret: bool | None = None
) -> jnp.ndarray:
    """Build the butterfly table for (B, K) weights; B, K multiples of W.

    Returns (B, K) laid out so that the (g, c) block equals the paper's
    W x W table block (row W-1 = running per-sample prefix).
    """
    from repro.kernels import runtime

    interpret = runtime.resolve_interpret(interpret)
    B, K = weights.shape
    assert B % W == 0 and K % W == 0, (B, K, W)
    G, nb = B // W, K // W
    grid = (G, nb)
    out = pl.pallas_call(
        functools.partial(_table_kernel, W=W),
        grid=grid,
        in_specs=[pl.BlockSpec((W, W), lambda g, c: (g, c))],
        out_specs=pl.BlockSpec((W, W), lambda g, c: (g, c)),
        out_shape=jax.ShapeDtypeStruct((B, K), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, W), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(weights)
    return out
