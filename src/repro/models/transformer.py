"""Unified model assembly for all assigned families.

dense / moe / vlm / audio  -> attention+FFN blocks, lax.scan over stacked
                              layer params (HLO size O(1) in depth)
ssm                        -> Mamba2 (SSD) blocks
hybrid (zamba2)            -> Mamba2 backbone + ONE shared attention+FFN
                              block applied every ``hybrid_attn_every`` layers

Three entrypoints per model: ``forward`` (train), ``prefill`` (build KV/SSM
cache, last-token logits), ``decode_step`` (one token against the cache).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.launch.sharding import shard
from repro.models import attention as attn
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.attention import GLOBAL_WINDOW
from repro.models.layers import dense_init, embed_lookup, rmsnorm


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_params(rng, cfg):
    L, D, V = cfg.n_layers, cfg.d_model, cfg.vocab_size
    ks = jax.random.split(rng, 8)
    params = {}
    if cfg.family != "audio":
        params["embed"] = {"tok": dense_init(ks[0], (V, D), in_axis=-1)}
    if cfg.family == "ssm":
        params["layers"] = {"ln1": jnp.zeros((L, D)),
                            "ssm": ssm_mod.init_mamba(ks[1], cfg, stack=L)}
    elif cfg.family == "hybrid":
        params["layers"] = {"ln1": jnp.zeros((L, D)),
                            "ssm": ssm_mod.init_mamba(ks[1], cfg, stack=L)}
        params["shared"] = {
            "ln1": jnp.zeros((D,)),
            "attn": attn.init_attention(ks[2], cfg),
            "ln2": jnp.zeros((D,)),
            "mlp": mlp_mod.init_mlp(ks[3], cfg),
        }
    else:
        layer = {"ln1": jnp.zeros((L, D)),
                 "attn": attn.init_attention(ks[2], cfg, stack=L),
                 "ln2": jnp.zeros((L, D))}
        if cfg.moe is not None:
            layer["moe"] = moe_mod.init_moe(ks[3], cfg, stack=L)
        else:
            layer["mlp"] = mlp_mod.init_mlp(ks[3], cfg, stack=L)
        params["layers"] = layer
    params["final_norm"] = jnp.zeros((D,))
    if not cfg.tie_embeddings and cfg.family != "audio":
        params["lm_head"] = dense_init(ks[4], (D, V))
    elif cfg.family == "audio":
        params["lm_head"] = dense_init(ks[4], (D, V))
    return params


def layer_windows(cfg, static: bool = False):
    """Per-layer attention window (int32), or None when no layer of ``cfg``
    has a sliding window (the kernels then compile without a window mask).
    GLOBAL_WINDOW marks the full-attention layers of a windowed stack.

    ``static=True`` (unrolled paths) returns a numpy array so each layer's
    window is a Python int at trace time — enabling windowed KV-cache
    reads."""
    import numpy as np
    if cfg.sliding_window is None:
        return None
    idx = np.arange(cfg.n_layers)
    is_global = (idx + 1) % (cfg.global_every or cfg.n_layers + 1) == 0
    out = np.where(is_global, GLOBAL_WINDOW,
                   cfg.sliding_window).astype(np.int32)
    return out if static else jnp.asarray(out)


def _embed(params, cfg, tokens, embeds):
    dt = jnp.dtype(cfg.dtype)
    if cfg.family == "audio":
        return embeds.astype(dt)
    h = embed_lookup(params["embed"]["tok"], tokens, dt)
    if cfg.family == "vlm" and embeds is not None:
        h = jnp.concatenate([embeds.astype(dt), h], axis=1)
    return h


def _unembed(params, cfg, h):
    dt = h.dtype
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        w = params["embed"]["tok"].astype(dt)
        logits = jnp.einsum("bsd,vd->bsv", h, w)
    else:
        logits = jnp.einsum("bsd,dv->bsv", h, params["lm_head"].astype(dt))
    return shard(logits, "batch", None, "vocab")


def _scan(body, carry, xs, unroll: bool = False):
    """lax.scan, or a python unroll (exact cost_analysis for the dry-run:
    XLA cost analysis counts a scan body ONCE, not x trip-count)."""
    if not unroll:
        return jax.lax.scan(body, carry, xs)
    L = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(L):
        xs_i = jax.tree.map(lambda x: x[i], xs)
        carry, y = body(carry, xs_i)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    else:
        ys = None
    return carry, ys


def _group_tree(tree, k):
    """Reshape stacked (L, ...) leaves to (L//k, k, ...)."""
    return jax.tree.map(lambda x: x.reshape((x.shape[0] // k, k) + x.shape[1:]),
                        tree)


# ---------------------------------------------------------------------------
# shared attn+FFN block bodies
# ---------------------------------------------------------------------------
def _attn_block(p_l, h, cfg, positions, window):
    hn = rmsnorm(h, p_l["ln1"], cfg.norm_eps)
    a, kv = attn.attention_prefill(p_l["attn"], hn, cfg, positions, window)
    h = h + a
    hn = rmsnorm(h, p_l["ln2"], cfg.norm_eps)
    return h, hn, kv


def _ffn(p_l, hn, cfg, n_groups):
    if "moe" in p_l:
        out, aux = moe_mod.apply_moe(p_l["moe"], hn, cfg, n_groups)
    else:
        out, aux = mlp_mod.apply_mlp(p_l["mlp"], hn, cfg), 0.0
    return out, aux


# ---------------------------------------------------------------------------
# train forward (no cache)
# ---------------------------------------------------------------------------
def _remat_wrap(body, remat):
    if not remat:
        return body
    if remat == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(body)


def forward(params, cfg, tokens=None, embeds=None, *, remat=False,
            n_groups: int = 1, unroll: bool = False):
    """Returns (logits (B,S,V) in cfg.dtype, aux_loss scalar fp32)."""
    h = _embed(params, cfg, tokens, embeds)
    h = shard(h, "batch", None, None)
    B, S, _ = h.shape
    positions = jnp.arange(S)[None, :]

    if cfg.family == "ssm":
        def body(h, p_l):
            hn = rmsnorm(h, p_l["ln1"], cfg.norm_eps)
            o, _ = ssm_mod.mamba_prefill(p_l["ssm"], hn, cfg)
            return h + o, None
        body = _remat_wrap(body, remat)
        h, _ = _scan(body, h, params["layers"], unroll)
        return _unembed(params, cfg, h), jnp.float32(0.0)

    if cfg.family == "hybrid":
        k = cfg.hybrid_attn_every
        grouped = _group_tree(params["layers"], k)
        shared = params["shared"]

        def group_body(h, pg):
            def mamba_body(h, p_l):
                hn = rmsnorm(h, p_l["ln1"], cfg.norm_eps)
                o, _ = ssm_mod.mamba_prefill(p_l["ssm"], hn, cfg)
                return h + o, None
            h, _ = _scan(mamba_body, h, pg, unroll)
            h, hn, _ = _attn_block(shared, h, cfg, positions, None)
            h = h + mlp_mod.apply_mlp(shared["mlp"], hn, cfg)
            return h, None
        group_body = _remat_wrap(group_body, remat)
        h, _ = _scan(group_body, h, grouped, unroll)
        return _unembed(params, cfg, h), jnp.float32(0.0)

    windows = layer_windows(cfg, static=unroll)

    def body(carry, xs):
        h, aux = carry
        p_l, w_l = xs
        h, hn, _ = _attn_block(p_l, h, cfg, positions, w_l)
        out, a = _ffn(p_l, hn, cfg, n_groups)
        return (h + out, aux + a), None

    body = _remat_wrap(body, remat)
    (h, aux), _ = _scan(body, (h, jnp.float32(0.0)),
                               (params["layers"], windows), unroll)
    return _unembed(params, cfg, h), aux


# ---------------------------------------------------------------------------
# prefill: build the cache, return last-token logits
# ---------------------------------------------------------------------------
def prefill(params, cfg, tokens=None, embeds=None, *, n_groups: int = 1,
            unroll: bool = False):
    h = _embed(params, cfg, tokens, embeds)
    h = shard(h, "batch", None, None)
    B, S, _ = h.shape
    positions = jnp.arange(S)[None, :]
    lengths = jnp.full((B,), S, jnp.int32)

    if cfg.family == "ssm":
        def body(h, p_l):
            hn = rmsnorm(h, p_l["ln1"], cfg.norm_eps)
            o, st = ssm_mod.mamba_prefill(p_l["ssm"], hn, cfg, return_state=True)
            return h + o, st
        h, (conv, state) = _scan(body, h, params["layers"], unroll)
        cache = {"conv": conv, "state": state, "length": lengths}
        return _unembed(params, cfg, h[:, -1:, :])[:, 0], cache

    if cfg.family == "hybrid":
        k = cfg.hybrid_attn_every
        grouped = _group_tree(params["layers"], k)
        shared = params["shared"]

        def group_body(h, pg):
            def mamba_body(h, p_l):
                hn = rmsnorm(h, p_l["ln1"], cfg.norm_eps)
                o, st = ssm_mod.mamba_prefill(p_l["ssm"], hn, cfg,
                                              return_state=True)
                return h + o, st
            h, (conv, state) = _scan(mamba_body, h, pg, unroll)
            h, hn, kv = _attn_block(shared, h, cfg, positions, None)
            h = h + mlp_mod.apply_mlp(shared["mlp"], hn, cfg)
            return h, (conv, state, kv[0].astype(jnp.dtype(cfg.dtype)),
                       kv[1].astype(jnp.dtype(cfg.dtype)))
        h, (conv, state, kc, vc) = _scan(group_body, h, grouped, unroll)
        # conv/state are (Gh, k, B, ...) -> flatten back to (L, B, ...)
        conv = jax.tree.map(
            lambda x: x.reshape((-1,) + x.shape[2:]), conv)
        state = jax.tree.map(
            lambda x: x.reshape((-1,) + x.shape[2:]), state)
        cache = {"conv": conv, "state": state, "k": kc, "v": vc,
                 "length": lengths}
        return _unembed(params, cfg, h[:, -1:, :])[:, 0], cache

    windows = layer_windows(cfg, static=unroll)

    def body(h, xs):
        p_l, w_l = xs
        h, hn, kv = _attn_block(p_l, h, cfg, positions, w_l)
        out, _ = _ffn(p_l, hn, cfg, n_groups)
        dt = jnp.dtype(cfg.dtype)
        return h + out, (kv[0].astype(dt), kv[1].astype(dt))

    h, (kc, vc) = _scan(body, h, (params["layers"], windows), unroll)
    kc = shard(kc, None, "batch", "kv_seq", "kv_heads", None)
    vc = shard(vc, None, "batch", "kv_seq", "kv_heads", None)
    cache = {"k": kc, "v": vc, "length": lengths}
    return _unembed(params, cfg, h[:, -1:, :])[:, 0], cache


# ---------------------------------------------------------------------------
# decode: one token against the cache
# ---------------------------------------------------------------------------
def _advance(lengths):
    """Each live row's length grows by the decoded token; a free row
    (length 0) stays at 0, so it never attends and never outgrows the
    cache however long it waits."""
    return jnp.where(lengths > 0, lengths + 1, 0)


def decode_step(params, cfg, cache, tokens=None, embeds=None,
                *, n_groups: int = 1, unroll: bool = False):
    """tokens (B,1) / embeds (B,1,D) -> (logits (B,V), new cache)."""
    h = _embed(params, cfg, tokens, embeds)
    lengths = cache["length"]

    if cfg.family == "ssm":
        def body(h, xs):
            p_l, conv_l, state_l = xs
            hn = rmsnorm(h, p_l["ln1"], cfg.norm_eps)
            o, nc, ns = ssm_mod.mamba_decode(p_l["ssm"], hn, cfg, conv_l, state_l)
            return h + o, (nc, ns)
        h, (conv, state) = _scan(
            body, h, (params["layers"], cache["conv"], cache["state"]),
            unroll)
        new_cache = {"conv": conv, "state": state,
                     "length": _advance(lengths)}
        return _unembed(params, cfg, h)[:, 0], new_cache

    if cfg.family == "hybrid":
        k = cfg.hybrid_attn_every
        grouped = _group_tree(params["layers"], k)
        shared = params["shared"]
        conv_g = _group_tree(cache["conv"], k)
        state_g = _group_tree(cache["state"], k)

        def group_body(h, xs):
            pg, conv_l, state_l, k_i, v_i = xs

            def mamba_body(h, xs_i):
                p_l, c_l, s_l = xs_i
                hn = rmsnorm(h, p_l["ln1"], cfg.norm_eps)
                o, nc, ns = ssm_mod.mamba_decode(p_l["ssm"], hn, cfg, c_l, s_l)
                return h + o, (nc, ns)
            h, (nconv, nstate) = _scan(mamba_body, h,
                                       (pg, conv_l, state_l), unroll)
            hn = rmsnorm(h, shared["ln1"], cfg.norm_eps)
            a, nk, nv = attn.attention_decode(shared["attn"], hn, cfg,
                                              k_i, v_i, lengths, None)
            h = h + a
            hn = rmsnorm(h, shared["ln2"], cfg.norm_eps)
            h = h + mlp_mod.apply_mlp(shared["mlp"], hn, cfg)
            return h, (nconv, nstate, nk, nv)

        h, (conv, state, kc, vc) = _scan(
            group_body, h,
            (grouped, conv_g, state_g, cache["k"], cache["v"]), unroll)
        conv = conv.reshape((-1,) + conv.shape[2:])
        state = state.reshape((-1,) + state.shape[2:])
        new_cache = {"conv": conv, "state": state, "k": kc, "v": vc,
                     "length": _advance(lengths)}
        return _unembed(params, cfg, h)[:, 0], new_cache

    windows = layer_windows(cfg, static=unroll)

    # xs/ys pattern: per-layer cache slices flow through the scan as inputs
    # and outputs (never a full-stack dynamic-update-slice chain, which XLA
    # cost analysis — and a non-aliasing compiler — would treat as an
    # O(L x cache) copy; with donation the ys buffer aliases the input).
    def body(h, xs):
        p_l, w_l, k_i, v_i = xs
        hn = rmsnorm(h, p_l["ln1"], cfg.norm_eps)
        a, nk, nv = attn.attention_decode(p_l["attn"], hn, cfg, k_i, v_i,
                                          lengths, w_l)
        h = h + a
        hn = rmsnorm(h, p_l["ln2"], cfg.norm_eps)
        out, _ = _ffn(p_l, hn, cfg, n_groups)
        return h + out, (nk, nv)

    h, (kc, vc) = _scan(body, h,
                        (params["layers"], windows, cache["k"], cache["v"]),
                        unroll)
    new_cache = {"k": kc, "v": vc, "length": _advance(lengths)}
    return _unembed(params, cfg, h)[:, 0], new_cache
