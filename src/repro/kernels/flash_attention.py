"""Flash attention Pallas TPU kernel (prefill/train path).

Online-softmax blocked attention with GQA, causal masking and an optional
sliding window. The window arrives as a scalar-prefetch operand (SMEM), so
one compiled kernel serves every layer of a scanned stack whatever its
window. Grid = (B, Hq, num_q_blocks, num_kv_blocks); the KV
axis is the innermost ("arbitrary") dimension and the running (m, l, acc)
state lives in VMEM scratch across KV iterations — the canonical TPU
flash-attention schedule (HBM->VMEM tiles, MXU for the two matmuls).

Block sizes are multiples of 128 on the MXU-facing dims (q/kv block length,
head_dim padded by the wrapper if needed).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(win_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, windowed: bool, bq: int, bk: int,
            s_orig: int, nk: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)                  # (bk, hd)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)

    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < s_orig
    if causal:
        mask &= kpos <= qpos
    if windowed:
        mask &= kpos > qpos - win_ref[0]
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                  # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                    interpret=False, block_q=128, block_k=128):
    """q (B,S,Hq,hd), k/v (B,S,Hkv,hd) -> (B,S,Hq,hd).

    ``window`` is None (full attention) or an int / int32 scalar, which may
    be traced."""
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    assert Hq % Hkv == 0
    group = Hq // Hkv
    scale = scale if scale is not None else hd ** -0.5

    bq = min(block_q, max(8, S))
    bk = min(block_k, max(8, S))
    s_pad = math.ceil(S / max(bq, bk)) * max(bq, bk)
    # layout: (B, H, S, hd) for clean 2D tiles
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if s_pad != S:
        pad = ((0, 0), (0, 0), (0, s_pad - S), (0, 0))
        qt, kt, vt = jnp.pad(qt, pad), jnp.pad(kt, pad), jnp.pad(vt, pad)
    nq, nk = s_pad // bq, s_pad // bk

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, windowed=window is not None,
        bq=bq, bk=bk, s_orig=S, nk=nk)
    win = jnp.asarray(0 if window is None else window, jnp.int32).reshape(1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j, w: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, w, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, w, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, i, j, w: (b, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, s_pad, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(win, qt, kt, vt)
    out = out[:, :, :S, :]
    return jnp.moveaxis(out, 1, 2)
