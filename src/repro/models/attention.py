"""GQA attention: train/prefill path + KV-cache decode path.

Supports: grouped-query attention, QKV bias, rotary embeddings, sliding
windows (static or per-layer traced, for gemma3's 5:1 local:global pattern).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.launch.sharding import shard
from repro.models.layers import dense_init, rotary

GLOBAL_WINDOW = jnp.iinfo(jnp.int32).max // 2  # "no window" sentinel
WINDOWED_DECODE_READS = False  # see note in attention_decode


def init_attention(rng, cfg, stack: int | None = None):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    lead = (stack,) if stack else ()
    ks = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(ks[0], lead + (d, hq * hd)),
        "wk": dense_init(ks[1], lead + (d, hkv * hd)),
        "wv": dense_init(ks[2], lead + (d, hkv * hd)),
        "wo": dense_init(ks[3], lead + (hq * hd, d), in_axis=-2),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros(lead + (hq * hd,))
        p["bk"] = jnp.zeros(lead + (hkv * hd,))
        p["bv"] = jnp.zeros(lead + (hkv * hd,))
    return p


def _project_qkv(p, x, cfg):
    """x: (B, S, D) -> q (B,S,Hq,hd), k/v (B,S,Hkv,hd)."""
    B, S, _ = x.shape
    dt = x.dtype
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = shard(q.reshape(B, S, cfg.n_heads, hd), "batch", None, "heads", None)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    return q, k, v


def attention_prefill(p, x, cfg, positions, window=None):
    """Full-sequence attention. Returns (out (B,S,D), (k, v) for the cache)."""
    q, k, v = _project_qkv(p, x, cfg)
    q = rotary(q, positions, cfg.rope_theta)
    k = rotary(k, positions, cfg.rope_theta)
    out = ops.flash_attention(q, k, v, causal=True, window=window)
    B, S = x.shape[:2]
    out = out.reshape(B, S, cfg.n_heads * cfg.resolved_head_dim)
    out = jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(x.dtype))
    return shard(out, "batch", None, None), (k, v)


def attention_decode(p, x1, cfg, k_cache, v_cache, lengths, window=None):
    """Single-token decode.

    x1: (B, 1, D); k_cache/v_cache: (B, S, Hkv, hd); lengths: (B,) valid
    entries per row, 0 for a free row, which attends nothing (its output
    is zero). Returns (out (B,1,D), new_k_cache, new_v_cache).
    """
    B = x1.shape[0]
    q, k, v = _project_qkv(p, x1, cfg)            # q (B,1,Hq,hd)
    pos = lengths.astype(jnp.int32)
    attended = jnp.where(pos > 0, pos + 1, 0)     # the new token's too
    q = rotary(q, pos[:, None], cfg.rope_theta)
    k = rotary(k, pos[:, None], cfg.rope_theta)

    # scatter new k/v at each row's write position
    def write(cache, val, i):
        return jax.lax.dynamic_update_slice(cache, val, (i, 0, 0))

    k_cache = shard(jax.vmap(write)(k_cache, k, pos),
                    "batch", "kv_seq", "kv_heads", None)
    v_cache = shard(jax.vmap(write)(v_cache, v, pos),
                    "batch", "kv_seq", "kv_heads", None)

    S = k_cache.shape[1]
    w = int(window) if isinstance(window, (int, jnp.integer,
                                           np.integer)) else None
    # NOTE: disabled by default — XLA SPMD lowers the per-row dynamic_slice
    # as a gather that replicates the cache operand (an all-gather per
    # layer), wiping out the read savings. The production fix is a
    # ring-buffer cache (w entries) for sliding-window layers; see
    # EXPERIMENTS.md §Perf (gemma) for the measured failure + design.
    if WINDOWED_DECODE_READS and w is not None and w < S:
        # windowed read: a sliding-window layer only ever attends to the
        # last `w` cache entries — slice before attention so HBM traffic is
        # O(w), not O(S) (the full cache is still updated above).
        start = jnp.clip(lengths.astype(jnp.int32) + 1 - w, 0, S - w)

        def win(c, st):
            return jax.lax.dynamic_slice(
                c, (st, 0, 0), (w, c.shape[1], c.shape[2]))
        k_eff = jax.vmap(win)(k_cache, start)
        v_eff = jax.vmap(win)(v_cache, start)
        len_eff = jnp.minimum(attended, w)
        out = ops.decode_attention(q[:, 0], k_eff, v_eff, len_eff,
                                   window=None)
    else:
        out = ops.decode_attention(q[:, 0], k_cache, v_cache, attended,
                                   window=window)
    out = out.reshape(B, 1, cfg.n_heads * cfg.resolved_head_dim)
    out = jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(x1.dtype))
    return shard(out, "batch", None, None), k_cache, v_cache
