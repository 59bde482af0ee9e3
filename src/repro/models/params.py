"""Declarative parameter specs: single source of truth for shapes, dtypes,
logical sharding axes and initializers.

`abstract_params(cfg)` (per model family) returns a pytree of `PSpec`
leaves; from it we derive (a) ShapeDtypeStructs for the dry-run, (b) real
initialized arrays for smoke tests / training, (c) PartitionSpecs via the
sharding rules. One tree, three views — structure mismatches are impossible.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quant import runtime_constant


@dataclasses.dataclass(frozen=True)
class PSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]   # logical axis per dim (None = replicated)
    dtype: str = "bf16"
    init: str = "normal"              # normal | zeros | ones
    fan_in_dims: Tuple[int, ...] = () # dims to normalize variance over

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)

    @property
    def jdtype(self):
        return {"bf16": jnp.bfloat16, "f32": jnp.float32,
                "u8": jnp.uint8, "i8": jnp.int8, "i32": jnp.int32}[self.dtype]


def is_pspec(x) -> bool:
    return isinstance(x, PSpec)


def to_shape_dtype(tree):
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.jdtype),
        tree, is_leaf=is_pspec)


def init_params(tree, key, dtype_override=None):
    """Materialize real arrays; each leaf gets a path-derived subkey."""
    leaves, treedef = jax.tree.flatten(tree, is_leaf=is_pspec)
    out = []
    for i, l in enumerate(leaves):
        k = jax.random.fold_in(key, i)
        dt = dtype_override or l.jdtype
        if l.init == "zeros" or l.dtype in ("u8", "i8", "i32"):
            out.append(jnp.zeros(l.shape, l.jdtype))
        elif l.init == "ones":
            out.append(jnp.ones(l.shape, dt))
        else:
            fan = 1
            dims = l.fan_in_dims or (tuple(range(len(l.shape) - 1))
                                     if len(l.shape) > 1 else (0,))
            for d in dims:
                fan *= l.shape[d]
            w = (jax.random.normal(k, l.shape, jnp.float32)
                 / runtime_constant(np.sqrt(max(fan, 1))))
            out.append(w.astype(dt))
    return jax.tree.unflatten(treedef, out)


def count_params(tree) -> int:
    leaves = jax.tree.leaves(tree, is_leaf=is_pspec)
    return sum(int(np.prod(l.shape)) for l in leaves)
