"""Pallas TPU fused RMSNorm (single HBM pass, f32 statistics)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(ms + eps)
                  * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm(x, scale, *, eps=1e-5, block_r=256, interpret=None):
    """x: (R, D) rows; scale: (D,)."""
    R, D = x.shape
    block_r = min(block_r, R)
    while R % block_r:
        block_r //= 2
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(R // block_r,),
        in_specs=[pl.BlockSpec((block_r, D), lambda i: (i, 0)),
                  pl.BlockSpec((D,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_r, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, D), x.dtype),
        name="rmsnorm",
        interpret=interpret,
    )(x, scale)
