"""Arithmetic shared by the reference models: RMSNorm and a projection in
float32 or, for the control, in float8 e4m3."""
from __future__ import annotations

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0
PRECISIONS = ("f32", "fp8")


def rmsnorm(x, w, eps):
    """x * rsqrt(mean(x^2) + eps) * (1 + w); w is stored as an offset."""
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def fp8(x, axis):
    """Round to float8 e4m3, scaled by the largest magnitude along
    ``axis`` (the contraction axis), and back to float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def linear(x, w, precision: str):
    """x (..., K) @ w (K, N), float32 accumulation."""
    if precision == "fp8":
        x, w = fp8(x, -1), fp8(w, 0)
    elif precision != "f32":
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    return jnp.einsum("...k,kn->...n", x, w)
