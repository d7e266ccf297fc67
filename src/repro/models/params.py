"""Parameter specs: single source of truth for shapes, logical sharding axes
and initialization.

Modules declare ``ParamSpec`` pytrees; the same tree materializes real
arrays (training/smoke tests), abstract ``ShapeDtypeStruct``s (the 512-device
dry-run never allocates), and per-leaf logical axes (the sharding rules
engine in ``repro.dist.sharding`` maps those to mesh axes).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]   # logical axis names, len == len(shape)
    init: str = "normal"              # normal | zeros | ones | scaled
    scale: float = 1.0                # stddev multiplier for normal init

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes}")


def is_spec(x: Any) -> bool:
    return isinstance(x, ParamSpec)


def _fan_in(spec: ParamSpec) -> int:
    """Axes-aware fan-in for the einsum contractions these params feed.

    'embed' anywhere but last => the contraction is over d_model (wq/wk/wv,
    w_gate/w_up, unembed, routers — including stacked/expert leading dims).
    'embed' last => the output is d_model; fan-in is everything else except
    batching dims (wo: heads*head_dim; w_down: d_ff).  Fallback: product of
    all but the last dim (minus stacked dims) — never *under*-estimates, so
    inits err small rather than exploding.
    """
    axes = spec.axes
    shape = spec.shape
    batchy = {"layers", "experts"}
    if "embed" in axes[:-1]:
        return shape[axes.index("embed")]
    prod = 1
    for name, size in zip(axes[:-1], shape[:-1]):
        if name in batchy:
            continue
        prod *= size
    return max(prod, 1)


@functools.partial(jax.jit, static_argnames=("spec", "dtype"))
def _leaf_init(key: jax.Array, spec: ParamSpec, dtype) -> jnp.ndarray:
    # one jitted program per leaf: the float32 normal, the scale and the
    # cast fuse into one pass, so a full-size stacked leaf is only ever
    # written in ``dtype`` (a bf16 4B-parameter model fits one 16 GiB chip)
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    # fan-in scaled normal: std = scale / sqrt(fan_in)
    std = spec.scale / np.sqrt(_fan_in(spec))
    return (jax.random.normal(key, spec.shape) * std).astype(dtype)


def init_params(key: jax.Array, specs, dtype=jnp.float32):
    """Materialize a spec tree into arrays (deterministic per-leaf keys)."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    arrs = [_leaf_init(k, s, dtype) for k, s in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, arrs)


def abstract_params(specs, dtype=jnp.bfloat16):
    """ShapeDtypeStruct tree — used by the dry-run (zero allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype),
        specs,
        is_leaf=is_spec,
    )


def logical_axes(specs):
    """Tree of logical-axis tuples, mirroring the params tree."""
    return jax.tree.map(lambda s: s.axes, specs, is_leaf=is_spec)


def param_count(specs) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=is_spec)
    return int(sum(int(np.prod(s.shape)) for s in leaves))


def param_bytes(specs, dtype=jnp.bfloat16) -> int:
    return param_count(specs) * jnp.dtype(dtype).itemsize


def stack_layer_specs(spec: ParamSpec, num_layers: int) -> ParamSpec:
    """Add a leading scanned-layers dimension to a spec."""
    return ParamSpec(
        shape=(num_layers,) + spec.shape,
        axes=("layers",) + spec.axes,
        init=spec.init,
        scale=spec.scale,
    )


def stack_specs_tree(specs, num_layers: int):
    return jax.tree.map(
        lambda s: stack_layer_specs(s, num_layers), specs, is_leaf=is_spec
    )
