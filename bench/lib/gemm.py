"""The plain reference of a GEMM, the number its output is judged by, and
the lower-precision control.

The reference multiplies in float32 at ``Precision.HIGHEST``.  A product
is judged by its normalised error max|out - ref| / max|ref|: a bf16
output rounds each element to 8 significant bits, so a sound product
reads a few times 2**-9, while a wrong tile, row or shard reads O(1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0      # largest finite float8_e4m3fn


def reference(a, b):
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


@jax.jit
def error(a, b, out):
    """max|out - a @ b| / max|a @ b|, on the device."""
    ref = reference(a, b)
    return (jnp.max(jnp.abs(out.astype(jnp.float32) - ref))
            / jnp.max(jnp.abs(ref)))


def fp8(x, axis):
    """``x`` rounded to float8 e4m3, scaled per slice along ``axis`` so the
    largest magnitude of each slice maps to the format's largest value."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@functools.partial(jax.jit, static_argnames="out_dtype")
def control(a, b, out_dtype=jnp.bfloat16):
    """The reference put in the program's place one precision step down:
    both operands in float8 e4m3 (per row of ``a``, per column of ``b``),
    float32 accumulation, output in ``out_dtype``."""
    return reference(fp8(a, -1), fp8(b, 0)).astype(out_dtype)
