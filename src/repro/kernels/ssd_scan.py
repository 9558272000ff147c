"""Mamba2 SSD chunked-scan Pallas TPU kernel.

Grid = (B, H, num_chunks); the chunk axis is sequential ("arbitrary") with
the running (P, N) state held in VMEM scratch — the TPU-native shape of the
SSD recurrence: the intra-chunk part is two MXU matmuls over (chunk x chunk)
and (chunk x N) tiles, the inter-chunk part is a rank-N state update that
never leaves VMEM. Chunk length and P/N are MXU-aligned by config (chunk a
multiple of 8, P/N of 16+).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, dtc_ref, a_ref, b_ref, c_ref, s0_ref, y_ref,
            sf_ref, state_scr, *, nc: int, chunk: int):
    h = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)          # (chunk, P)
    A = a_ref[h]                                 # scalar (SMEM)
    a_row = dt_ref[0, 0].astype(jnp.float32) * A     # (1, chunk) log-decay
    dt_col = dtc_ref[0, 0].astype(jnp.float32)       # (chunk, 1)
    a_col = dt_col * A
    Bm = b_ref[0].astype(jnp.float32)            # (chunk, N)
    Cm = c_ref[0].astype(jnp.float32)            # (chunk, N)

    # in-chunk cumsum as masked sums (no cumsum lowering on Mosaic), in
    # both layouts so no vector is ever transposed
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = row >= col
    cs_col = jnp.sum(jnp.where(tril, a_row, 0.0), axis=1,
                     keepdims=True)              # (chunk, 1)
    cs_row = jnp.sum(jnp.where(row <= col, a_col, 0.0), axis=0,
                     keepdims=True)              # (1, chunk)
    total = jnp.sum(a_row, axis=1, keepdims=True)    # (1, 1)
    L = jnp.where(tril, jnp.exp(cs_col - cs_row), 0.0)  # (l, s)

    dtx = dt_col * x                             # (chunk, P)
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    y_diag = jax.lax.dot_general(CB * L, dtx, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    state = state_scr[...]                       # (P, N)
    y_off = jax.lax.dot_general(Cm, state, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_off = y_off * jnp.exp(cs_col)              # (chunk, P)
    y_ref[0, 0] = (y_diag + y_off).astype(y_ref.dtype)

    decay_tail = jnp.exp(total - cs_col)         # (chunk, 1)
    new_contrib = jax.lax.dot_general(
        dtx, Bm * decay_tail, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)      # (P, N)
    state_scr[...] = state * jnp.exp(total) + new_contrib

    @pl.when(ci == nc - 1)
    def _finish():
        sf_ref[0, 0] = state_scr[...]


def ssd_scan(x, dt, A, Bm, Cm, *, chunk=64, init_state=None,
             return_state=False, interpret=False):
    """x (B,S,H,P), dt (B,S,H), A (H,), Bm/Cm (B,S,N) -> y (B,S,H,P)
    [, final_state (B,H,P,N) f32]."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, max(8, S))
    s_pad = math.ceil(S / chunk) * chunk
    if s_pad != S:
        # dt=0 padding: decay 1, contribution 0 (state-exact; see ref.py)
        x = jnp.pad(x, ((0, 0), (0, s_pad - S), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, s_pad - S), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, s_pad - S), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, s_pad - S), (0, 0)))
    nc = s_pad // chunk

    xt = jnp.moveaxis(x, 2, 1)                   # (B, H, S, P)
    dtt = jnp.moveaxis(dt, 2, 1)[:, :, None, :]  # (B, H, 1, S)
    dtc = jnp.moveaxis(dt, 2, 1)[..., None]       # (B, H, S, 1)
    s0 = (jnp.zeros((B, H, P, N), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    kernel = functools.partial(_kernel, nc=nc, chunk=chunk)
    y, sf = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),  # A: whole (H,) in SMEM
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, s_pad, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xt, dtt, dtc, A.astype(jnp.float32), Bm, Cm, s0)
    y = jnp.moveaxis(y, 1, 2)[:, :S]
    if return_state:
        return y, sf
    return y
