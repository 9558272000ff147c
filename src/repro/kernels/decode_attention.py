"""Flash-decode Pallas TPU kernel: one query token per sequence against a
(possibly partially filled) KV cache.

Grid = (B, num_kv_blocks); each instance processes ALL query heads of one
sequence (the whole q row fits VMEM easily: Hq x hd). The KV axis is the
innermost "arbitrary" dimension with the online-softmax state in VMEM
scratch. Per-row valid lengths and the sliding window arrive as
scalar-prefetch operands (SMEM), and a traced window compiles like a
static one.

Only a row's live blocks are read: the K/V index maps hold each row at
its last valid block (and a windowed row at its first in-window block),
and the pipeline issues no copy while consecutive grid steps name the
same block. The steps past a row's live blocks skip their compute, so a
row of length 0 (a free serving slot) costs one block fetch and returns
zeros. Lengths past the cache are read as the whole cache.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, win_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
            acc_scr, *, scale: float, windowed: bool, bk: int, nk: int,
            group: int):
    b = pl.program_id(0)
    ki = pl.program_id(1)
    length = len_ref[b]
    live = ki * bk < length
    if windowed:
        live &= (ki + 1) * bk > length - win_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale         # (Hq, hd)
        k = k_ref[0].astype(jnp.float32)                 # (bk, Hkv, hd)
        v = v_ref[0].astype(jnp.float32)
        Hq = q.shape[0]
        Hkv = k.shape[1]
        qg = q.reshape(Hkv, group, q.shape[-1])
        # s (Hkv, group, bk)
        s = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)          # (Hkv, group, bk)
        kpos = ki * bk + jax.lax.broadcasted_iota(
            jnp.int32, (Hkv, group, bk), 2)
        mask = kpos < length
        if windowed:
            mask &= kpos > length - 1 - win_ref[0]
        s = jnp.where(mask, s, NEG_INF)
        s = s.reshape(Hq, bk)

        m_prev = m_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                           # (Hq, bk)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        pg = p.reshape(Hkv, group, bk)
        pv = jax.lax.dot_general(
            pg, v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)          # (Hkv, group, hd)
        acc_scr[...] = acc_scr[...] * alpha + pv.reshape(Hq, -1)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def _kv_block(b, j, lens, win, *, bk, nk, windowed):
    """Grid step (b, j) reads KV block j held to row b's live blocks:
    from the window's first block (windowed) to the last valid one."""
    length = lens[b]
    last = jnp.clip(jax.lax.div(length - 1, bk), 0, nk - 1)
    if windowed:
        first = jnp.clip(jax.lax.div(length - win[0], bk), 0, last)
        j = jnp.maximum(j, first)
    return b, jnp.minimum(j, last), 0, 0


def decode_attention(q, k_cache, v_cache, lengths, *, window=None,
                     scale=None, interpret=False, block_k=None):
    """q (B,Hq,hd), k/v cache (B,S,Hkv,hd), lengths (B,) -> (B,Hq,hd).

    ``window`` is None (full attention) or an int / int32 scalar, which may
    be traced. ``block_k`` defaults to 256 keys for head dims up to 128 and
    128 above: at hd 256 a 256-key block overflows the TPU's scoped VMEM."""
    B, Hq, hd = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    group = Hq // Hkv
    scale = scale if scale is not None else hd ** -0.5
    if block_k is None:
        block_k = 256 if hd <= 128 else 128

    bk = min(block_k, S)
    s_pad = math.ceil(S / bk) * bk
    if s_pad != S:
        pad = ((0, 0), (0, s_pad - S), (0, 0), (0, 0))
        k_cache, v_cache = jnp.pad(k_cache, pad), jnp.pad(v_cache, pad)
    nk = s_pad // bk

    windowed = window is not None
    kernel = functools.partial(_kernel, scale=scale, windowed=windowed,
                               bk=bk, nk=nk, group=group)
    kv_block = functools.partial(_kv_block, bk=bk, nk=nk, windowed=windowed)
    win = jnp.asarray(0 if window is None else window, jnp.int32).reshape(1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nk),
        in_specs=[
            pl.BlockSpec((1, Hq, hd), lambda b, j, lens, w: (b, 0, 0)),
            pl.BlockSpec((1, bk, Hkv, hd), kv_block),
            pl.BlockSpec((1, bk, Hkv, hd), kv_block),
        ],
        out_specs=pl.BlockSpec((1, Hq, hd), lambda b, j, lens, w: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attention",
    )(jnp.clip(lengths.astype(jnp.int32), 0, S), win, q, k_cache, v_cache)
    return out
