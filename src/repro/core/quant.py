"""Quantization primitives shared by the AMC storage planes.

Symmetric integer quantization with per-channel (or per-group) scales.
These are the "sensing"/"writing" circuits of the software-defined
augmented memory: `quantize` is the write driver, `dequantize` the sense
amplifier. Stochastic rounding plays the role of the paper's word-line
boosting — it lets weak writes (values below half an LSB) land on the
correct level in expectation.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

INT4_MAX = 7        # symmetric int4: [-7, 7] (-8 reserved, keeps negation closed)
INT8_MAX = 127


def runtime_constant(c, dtype=jnp.float32) -> jax.Array:
    """`c` as a value XLA cannot fold. Under jit, XLA rewrites x / c into
    x * (1 / c), which rounds differently; dividing by this instead keeps
    a jitted quotient bit-identical to the eager one (the serving engine
    jits its weight initializer and packer, whose eager results the
    golden tests pin, and the jitted steps quantize with the same scales
    as the host packers)."""
    return jax.lax.optimization_barrier(jnp.asarray(c, dtype))


def absmax_scale(x: jax.Array, axis=None, qmax: int = INT4_MAX,
                 eps: float = 1e-8) -> jax.Array:
    """Per-axis symmetric scale so that max|x| maps to qmax (a true
    division, jitted or not: see `runtime_constant`)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    return jnp.maximum(amax, eps) / runtime_constant(qmax, amax.dtype)


def quantize(x: jax.Array, scale: jax.Array, qmax: int,
             stochastic: bool = False,
             key: Optional[jax.Array] = None) -> jax.Array:
    """Symmetric quantize to signed ints in [-qmax, qmax] (int8 container)."""
    y = x / scale
    if stochastic:
        if key is None:
            raise ValueError("stochastic rounding requires a PRNG key")
        noise = jax.random.uniform(key, y.shape, dtype=y.dtype) - 0.5
        q = jnp.floor(y + 0.5 + noise)
    else:
        q = jnp.round(y)
    return jnp.clip(q, -qmax, qmax).astype(jnp.int8)


def dequantize(q: jax.Array, scale: jax.Array,
               dtype=jnp.bfloat16) -> jax.Array:
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)).astype(dtype)


def quantize_int4(x: jax.Array, axis=-1, stochastic: bool = False,
                  key: Optional[jax.Array] = None):
    """Returns (q:int8 in [-7,7], scale) with per-`axis` scales."""
    scale = absmax_scale(x, axis=axis, qmax=INT4_MAX)
    return quantize(x, scale, INT4_MAX, stochastic, key), scale


def quantize_int8(x: jax.Array, axis=-1, stochastic: bool = False,
                  key: Optional[jax.Array] = None):
    scale = absmax_scale(x, axis=axis, qmax=INT8_MAX)
    return quantize(x, scale, INT8_MAX, stochastic, key), scale


# ---------------------------------------------------------------------------
# int4 <-> uint8 nibble packing (two int4 values per byte).
# ---------------------------------------------------------------------------

def pack_int4_pair(hi: jax.Array, lo: jax.Array) -> jax.Array:
    """Pack two int4 tensors (int8 storage, values in [-8,7]) into one uint8.

    `hi` occupies the high nibble, `lo` the low nibble. Shapes must match.
    This is the 8T dual-bit cell: one physical byte, two logical values.
    """
    hi_u = jnp.bitwise_and(hi.astype(jnp.uint8), jnp.uint8(0x0F))
    lo_u = jnp.bitwise_and(lo.astype(jnp.uint8), jnp.uint8(0x0F))
    return jnp.bitwise_or(jnp.left_shift(hi_u, 4), lo_u)


def unpack_int4_hi(packed: jax.Array) -> jax.Array:
    """Extract the high nibble as sign-extended int8 (the static plane)."""
    # arithmetic shift on int8 sign-extends the high nibble
    return jnp.right_shift(packed.astype(jnp.int8), 4)


def unpack_int4_lo(packed: jax.Array) -> jax.Array:
    """Extract the low nibble as sign-extended int8 (the dynamic plane)."""
    shifted = jnp.left_shift(packed.astype(jnp.uint8), 4).astype(jnp.int8)
    return jnp.right_shift(shifted, 4)


def unpack_int4_pair(packed: jax.Array):
    return unpack_int4_hi(packed), unpack_int4_lo(packed)
