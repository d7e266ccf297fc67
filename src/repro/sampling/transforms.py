"""Composable logit transforms for truncated decode sampling.

Every real decode workload truncates before it samples — top-k, nucleus
(top-p), min-p — and the classic implementation bolts truncation on via a
full descending sort of the vocabulary (write a (B, K) sorted copy, scan
its cumsum, scatter the mask back).  That sorted copy is *exactly* the
materialization the butterfly table exists to avoid, so this module
restates all three truncations in the form the butterfly path already
speaks: a **per-row weight threshold**.

  * ``TopK(k)``   keeps the k largest weights.  The k-th order statistic
    is a monotone function of "how many weights are >= tau", so it is
    found by bisection on the *value* axis: log2(1/eps) masked counts
    instead of one K log K sort.
  * ``TopP(p)``   keeps the smallest set of largest weights whose mass
    reaches p.  The nucleus boundary value is the largest tau with
    ``sum(w[w >= tau]) >= p * total`` — again monotone in tau, again a
    bisection, this time over masked *sums* (the same block-sum shapes
    butterfly pass A already produces; DESIGN.md §7).
  * ``MinP(p)``   keeps weights >= p * max(w): one row-max, no search.
  * ``Temperature(t)`` rescales logits before the softmax (composable,
    per-row capable, folded into :func:`apply_to_logits`).

Transforms are registered pytrees whose parameters are **leaves** — a
``TopP(p)`` with a traced (B,) ``p`` flows through ``jax.jit`` like any
other operand, so one compiled decode step serves per-request (even
per-row heterogeneous) truncation parameters with zero retraces.

Chains compose sequentially, exactly like sorted-reference processors:
each truncation operates on the survivors of the previous one.  Because
every stage is a threshold and threshold sets nest, a chain reduces to a
single per-row scalar ``tau`` — no intermediate (B, K) masks.

Execution surfaces:

  * :func:`thresholds` / :func:`apply` / :func:`apply_to_logits` — the
    pure-XLA twin (any backend; emits no ``sort``/``top_k`` primitive).
  * the fused Pallas kernels in ``repro.kernels.butterfly_sample`` fold
    the same bisection into the butterfly draw's pass A: the weight tile
    is already VMEM-resident, so the search costs iterations of on-chip
    reductions instead of HBM sweeps.
  * ``repro.sampling.reference`` — the sort-based oracle the tests
    compare against.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

# bisection iterations for the value-axis threshold search.  The search
# runs on the uint32 *bit patterns* of the nonnegative float32 weights —
# IEEE bit patterns of nonnegative floats are monotonically ordered, so 32
# halvings of the bit-space bracket converge EXACTLY to the boundary
# weight value, whatever the dynamic range (softmax tails 30 orders of
# magnitude below the mode included).  The fused mask therefore equals
# the sorted-reference mask bit-for-bit on distinct weights (tests pin
# this across the K/W grid).
SEARCH_ITERS = 32


@dataclasses.dataclass(frozen=True)
class Temperature:
    """Divide logits by ``t`` before the softmax.  ``t`` may be a scalar
    or a per-row (B,) array (per-request temperature)."""

    t: Any = 1.0


@dataclasses.dataclass(frozen=True)
class TopK:
    """Keep the ``k`` largest weights per row (ties at the boundary value
    are kept, as with a value threshold).  ``k <= 0`` disables.  ``k``
    may be a scalar or a per-row (B,) array."""

    k: Any = 0


@dataclasses.dataclass(frozen=True)
class TopP:
    """Nucleus truncation: keep the smallest prefix of descending weights
    whose probability mass reaches ``p`` (the boundary token included).
    ``p >= 1`` disables.  Scalar or per-row (B,)."""

    p: Any = 1.0


@dataclasses.dataclass(frozen=True)
class MinP:
    """Keep tokens whose probability is at least ``p`` times the modal
    probability.  ``p <= 0`` disables.  Scalar or per-row (B,)."""

    p: Any = 0.0


for _cls, _field in ((Temperature, "t"), (TopK, "k"), (TopP, "p"), (MinP, "p")):
    jax.tree_util.register_pytree_node(
        _cls,
        (lambda f: lambda obj: ((getattr(obj, f),), None))(_field),
        (lambda c: lambda aux, children: c(children[0]))(_cls),
    )

TRUNCATIONS = (TopK, TopP, MinP)
_SIG_LETTER = {Temperature: "t", TopK: "k", TopP: "p", MinP: "m"}


def _static_scalar(v) -> bool:
    return isinstance(v, (int, float, bool))


def chain(
    temperature: Any = None,
    top_k: Any = None,
    top_p: Any = None,
    min_p: Any = None,
) -> Tuple:
    """Build the canonical transform chain (temperature, then top-k, then
    top-p, then min-p — the order every major serving stack applies).

    ``None`` omits a stage, and so does a *statically* disabling scalar
    (``top_k=0``, ``top_p>=1``, ``min_p<=0``, ``temperature=1``): a
    stage that provably does nothing should not cost its threshold
    search on the decode hot path.  Arrays/tracers are always kept —
    per-row values decide enablement at runtime, inside one executable."""
    out = []
    if temperature is not None and not (
        _static_scalar(temperature) and temperature == 1
    ):
        out.append(Temperature(temperature))
    if top_k is not None and not (_static_scalar(top_k) and top_k <= 0):
        out.append(TopK(top_k))
    if top_p is not None and not (_static_scalar(top_p) and top_p >= 1.0):
        out.append(TopP(top_p))
    if min_p is not None and not (_static_scalar(min_p) and min_p <= 0.0):
        out.append(MinP(min_p))
    return tuple(out)


def signature(transforms: Optional[Sequence]) -> str:
    """Static signature of a chain — the transform *types* in order,
    independent of parameter values.  Joins plan memo keys: workloads
    that truncate plan separately from ones that don't, but two different
    ``p`` values share one plan and one compiled executable."""
    if not transforms:
        return ""
    return "".join(_SIG_LETTER[type(t)] for t in transforms)


def validate(transforms: Sequence) -> None:
    for t in transforms:
        if type(t) not in _SIG_LETTER:
            raise ValueError(
                f"unknown transform {t!r}; options: Temperature, TopK, "
                "TopP, MinP (see repro.sampling.transforms)"
            )


def _row(v, B: int) -> jnp.ndarray:
    """Broadcast a scalar-or-(B,) parameter to a float32 (B,) vector."""
    v = jnp.asarray(v, jnp.float32)
    if v.ndim == 0:
        return jnp.broadcast_to(v, (B,))
    if v.shape != (B,):
        raise ValueError(
            f"per-row transform parameter must be scalar or ({B},), got "
            f"shape {v.shape}"
        )
    return v


def _f2b(x):
    """float32 -> uint32 bit pattern (monotone for nonnegative floats)."""
    return jax.lax.bitcast_convert_type(x, jnp.uint32)


def _b2f(b):
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def _bisect(lo, hi, keep_fn, iters: int):
    """Vectorized value-axis bisection over float *bit space*.
    ``keep_fn(tau) -> (B,) bool`` must be True at ``lo`` and monotonically
    switch to False by ``hi``; returns the largest representable float32
    still True — exact after 32 iterations, because uint32 bit patterns
    of nonnegative floats order like the floats and the bit bracket
    halves each step.

    This is the dyadic walk of the butterfly search transplanted from the
    index axis to the value axis: each step halves the bracket with one
    masked reduction, the way each butterfly level halves the index range
    with one partial-sum comparison (DESIGN.md §7)."""

    def body(_, lh):
        lo_b, hi_b = lh
        mid_b = lo_b + ((hi_b - lo_b) >> jnp.uint32(1))
        keep = keep_fn(_b2f(mid_b))
        return jnp.where(keep, mid_b, lo_b), jnp.where(keep, hi_b, mid_b)

    lo_b, hi_b = jax.lax.fori_loop(0, iters, body, (_f2b(lo), _f2b(hi)))
    return _b2f(lo_b)


def _above_max(wf):
    """nextafter(rowmax, inf): one bit above the row maximum — the open
    upper end of the threshold bracket."""
    return _b2f(_f2b(jnp.max(wf, axis=-1, keepdims=True)) + jnp.uint32(1))


# The stage helpers below work on (B, 1) columns (k/p/tau): the same code
# then traces inside a TPU kernel body, which has no 1-D vector layouts.


def _topk_tau(wf, k, tau0, iters: int):
    hi = _above_max(wf)

    def keeps(tau):
        kept = jnp.sum((wf >= tau).astype(jnp.float32), axis=-1, keepdims=True)
        return kept >= k

    tau = _bisect(tau0, hi, keeps, iters)
    return jnp.where(k > 0, jnp.maximum(tau, tau0), tau0)


def _topp_tau(wf, p, tau0, iters: int):
    hi = _above_max(wf)
    def mass(tau):
        return jnp.sum(jnp.where(wf >= tau, wf, 0.0), axis=-1, keepdims=True)

    target = p * mass(tau0)

    def keeps(tau):
        return mass(tau) >= target

    tau = _bisect(tau0, hi, keeps, iters)
    return jnp.where(p < 1.0, jnp.maximum(tau, tau0), tau0)


def _minp_tau(wf, p, tau0):
    rowmax = jnp.max(wf, axis=-1, keepdims=True)
    return jnp.where(p > 0.0, jnp.maximum(tau0, p * rowmax), tau0)


def thresholds(
    weights, transforms: Sequence, iters: int = SEARCH_ITERS
) -> jnp.ndarray:
    """Reduce a truncation chain to one per-row float32 threshold: token j
    of row b survives iff ``weights[b, j] >= thresholds[b]``.

    Stages compose sequentially (each operates on the previous stage's
    survivors), which the nesting of threshold sets turns into a running
    ``tau`` — never an intermediate (B, K) mask, never a sort."""
    validate(transforms)
    wf = jnp.asarray(weights).astype(jnp.float32)
    B = wf.shape[0]
    tau = jnp.zeros((B, 1), jnp.float32)
    for t in transforms:
        if isinstance(t, TopK):
            tau = _topk_tau(wf, _row(t.k, B)[:, None], tau, iters)
        elif isinstance(t, TopP):
            tau = _topp_tau(wf, _row(t.p, B)[:, None], tau, iters)
        elif isinstance(t, MinP):
            tau = _minp_tau(wf, _row(t.p, B)[:, None], tau)
        elif isinstance(t, Temperature):
            raise ValueError(
                "Temperature acts on logits, not weights — fold it via "
                "apply_to_logits(transforms, logits) or the temperature= "
                "argument"
            )
    return tau[:, 0]


def apply(weights, transforms: Sequence, iters: int = SEARCH_ITERS):
    """Masked weights: the materializing XLA twin every table-building
    variant consumes (zero weights are never selected by any draw path,
    so masking *is* truncation for prefix/fenwick/butterfly/two_level/
    alias state builds)."""
    transforms = tuple(t for t in transforms if not isinstance(t, Temperature))
    if not transforms:
        return jnp.asarray(weights)
    weights = jnp.asarray(weights)
    tau = thresholds(weights, transforms, iters=iters)
    keep = weights.astype(jnp.float32) >= tau[:, None]
    return jnp.where(keep, weights, jnp.zeros_like(weights))


def temperature_of(transforms: Optional[Sequence], temperature: Any = 1.0):
    """The effective sampling temperature: the ``temperature=`` argument
    composed (multiplicatively) with every Temperature in the chain."""
    t = temperature
    for tr in transforms or ():
        if isinstance(tr, Temperature):
            t = t * jnp.asarray(tr.t) if not _is_one(tr.t) else t
    return t


def _is_one(v) -> bool:
    return isinstance(v, (int, float)) and v == 1


def truncations_of(transforms: Optional[Sequence]) -> Tuple:
    return tuple(
        t for t in transforms or () if not isinstance(t, Temperature)
    )


def apply_to_logits(
    transforms: Optional[Sequence],
    logits,
    temperature: Any = 1.0,
    iters: int = SEARCH_ITERS,
):
    """Logits -> truncated weights: temperature-scaled stable softmax
    (Temperature stages folded in), then the truncation chain's mask."""
    from repro.sampling.distribution import logits_to_weights

    w = logits_to_weights(logits, temperature_of(transforms, temperature))
    return apply(w, truncations_of(transforms), iters=iters)


def canonical_params(
    transforms: Optional[Sequence], B: int
) -> Optional[jnp.ndarray]:
    """The (B, 3) float32 ``[k, p, min_p]`` parameter block the fused
    kernels consume — or ``None`` when the chain is not expressible as
    the canonical top-k -> top-p -> min-p order (at most one of each, in
    order; the XLA twin handles arbitrary chains)."""
    trunc = truncations_of(transforms)
    order = {TopK: 0, TopP: 1, MinP: 2}
    seen = [order[type(t)] for t in trunc if type(t) in order]
    if len(seen) != len(trunc) or seen != sorted(set(seen)):
        return None
    k = p = m = None
    for t in trunc:
        if isinstance(t, TopK):
            k = t.k
        elif isinstance(t, TopP):
            p = t.p
        elif isinstance(t, MinP):
            m = t.p
    return jnp.stack(
        [
            _row(0 if k is None else k, B),
            _row(1.0 if p is None else p, B),
            _row(0.0 if m is None else m, B),
        ],
        axis=1,
    )


def thresholds_from_params(
    weights, params, iters: int = SEARCH_ITERS
) -> jnp.ndarray:
    """Per-row tau from a (B, 3) ``[k, p, min_p]`` block — the XLA-side
    half of the two-pass kernel route (vocab-scale tiles compute tau here,
    then run masked pass A / masked walk; DESIGN.md §7)."""
    return threshold_column(weights, params, iters)[:, 0]


def threshold_column(weights, params, iters: int = SEARCH_ITERS) -> jnp.ndarray:
    """:func:`thresholds_from_params` as a (B, 1) column — the form the
    fused truncated kernels trace in-kernel."""
    wf = jnp.asarray(weights).astype(jnp.float32)
    params = jnp.asarray(params, jnp.float32)
    tau = jnp.zeros((wf.shape[0], 1), jnp.float32)
    tau = _topk_tau(wf, params[:, 0:1], tau, iters)
    tau = _topp_tau(wf, params[:, 1:2], tau, iters)
    return _minp_tau(wf, params[:, 2:3], tau)
