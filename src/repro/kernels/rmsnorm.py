"""Fused RMSNorm Pallas TPU kernel (row-tiled, fp32 accumulation)."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    w = 1.0 + w_ref[...].astype(jnp.float32)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * w).astype(o_ref.dtype)


def rmsnorm(x, w, *, eps: float = 1e-5, interpret: bool = False,
            block_rows: int = 256):
    shape = x.shape
    D = shape[-1]
    x2 = x.reshape(-1, D)
    R = x2.shape[0]
    br = min(block_rows, R)
    grid = (math.ceil(R / br),)
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, D), x.dtype),
        interpret=interpret,
        name="rmsnorm",
    )(x2, w)
    return out.reshape(shape)
