"""Plain float32 reference of a Qwen2 decoder (Qwen2ForCausalLM), from its
published description and ``config.json``. It imports nothing of the
program under test, and makes its weights itself from the seed.

Layer: h += Wo . attn(rope(rmsnorm(h) Wq + bq), rope(... Wk + bk),
... Wv + bv); h += Wd (silu(x Wg) * x Wu) with x = rmsnorm(h). Grouped-
query attention, causal, scale 1/sqrt(head_dim); rotary embedding by
half rotation with theta = rope_theta; tied embeddings.

One departure from the checkpoint's parameterisation, shared with the
program's parameter tree: each RMSNorm stores its weight as an offset from
one (scale = 1 + w). The arithmetic is the published one.

``precision="fp8"`` is the control: every projection, and the head, takes
its inputs and weights rounded to float8 e4m3 (scaled per row and per
output column by the largest magnitude), the step below the bfloat16 that
the configuration serves in. Everything else stays float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.numerics import linear, rmsnorm


def dims(cfg: dict) -> dict:
    c = cfg["config"]
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    return {"L": c["num_hidden_layers"], "D": d, "H": h,
            "KV": c["num_key_value_heads"],
            "hd": c.get("head_dim") or d // h, "F": c["intermediate_size"],
            "V": c["vocab_size"], "eps": c["rms_norm_eps"],
            "theta": c["rope_theta"]}


def layout(cfg: dict) -> dict:
    """Paths, shapes and inits of the parameter tree."""
    m = dims(cfg)
    L, D, H, KV, hd, F, V = (m[k] for k in ("L", "D", "H", "KV", "hd",
                                            "F", "V"))

    def w(shape, fan_in):
        return {"shape": shape, "stacked": True,
                "init": {"kind": "sym", "std": fan_in ** -0.5}}

    norm = {"kind": "sym", "std": 0.05}
    bias = {"kind": "sym", "std": 0.05}
    return {
        "embed/tok": {"shape": [V, D], "init": {"kind": "sym",
                                                 "std": D ** -0.5}},
        "layers/ln1": {"shape": [L, D], "stacked": True, "init": norm},
        "layers/ln2": {"shape": [L, D], "stacked": True, "init": norm},
        "layers/attn/wq": w([L, D, H * hd], D),
        "layers/attn/wk": w([L, D, KV * hd], D),
        "layers/attn/wv": w([L, D, KV * hd], D),
        "layers/attn/wo": w([L, H * hd, D], H * hd),
        "layers/attn/bq": {"shape": [L, H * hd], "stacked": True,
                           "init": bias},
        "layers/attn/bk": {"shape": [L, KV * hd], "stacked": True,
                           "init": bias},
        "layers/attn/bv": {"shape": [L, KV * hd], "stacked": True,
                           "init": bias},
        "layers/mlp/w_gate": w([L, D, F], D),
        "layers/mlp/w_up": w([L, D, F], D),
        "layers/mlp/w_down": w([L, F, D], F),
        "final_norm": {"shape": [D], "init": norm},
    }


def _rope(x, theta):
    """x (B, T, heads, hd), positions 0..T-1."""
    hd = x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("dims_", "precision"))
def _layer(W, h, *, dims_, precision):
    m = dict(dims_)
    B, T, D = h.shape
    H, KV, hd = m["H"], m["KV"], m["hd"]
    x = rmsnorm(h, W["layers/ln1"], m["eps"])
    q = linear(x, W["layers/attn/wq"], precision) + W["layers/attn/bq"]
    k = linear(x, W["layers/attn/wk"], precision) + W["layers/attn/bk"]
    v = linear(x, W["layers/attn/wv"], precision) + W["layers/attn/bv"]
    q = _rope(q.reshape(B, T, H, hd), m["theta"])
    k = _rope(k.reshape(B, T, KV, hd), m["theta"])
    v = v.reshape(B, T, KV, hd)
    g = H // KV
    q = q.reshape(B, T, KV, g, hd)
    s = jnp.einsum("btkgd,bskd->bkgts", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bkgts,bskd->btkgd", p, v).reshape(B, T, H * hd)
    h = h + linear(a, W["layers/attn/wo"], precision)
    x = rmsnorm(h, W["layers/ln2"], m["eps"])
    up = linear(x, W["layers/mlp/w_up"], precision)
    gate = linear(x, W["layers/mlp/w_gate"], precision)
    return h + linear(jax.nn.silu(gate) * up, W["layers/mlp/w_down"],
                      precision)


def final_hidden(cfg: dict, tokens, layer_weights, leaf, precision: str):
    """tokens (B, T) int32 -> the final normed hidden state (B, T, D),
    float32. ``layer_weights(l)`` gives layer l's float32 leaves by path,
    ``leaf(path)`` a leaf that is not stacked."""
    m = dims(cfg)
    h = jnp.take(leaf("embed/tok"), tokens, axis=0)
    with jax.default_matmul_precision("highest"):
        for layer in range(m["L"]):
            h = _layer(layer_weights(layer), h,
                       dims_=tuple(sorted(m.items())), precision=precision)
        return rmsnorm(h, leaf("final_norm"), m["eps"])


def head(cfg: dict, leaf):
    """The (V, D) matrix whose rows score each token (tied embeddings)."""
    return leaf("embed/tok")



def step_flops(cfg: dict, kind: str, lengths: list) -> float:
    """Operations a step needs. ``decode``: one token for each active
    slot, ``lengths`` its attended keys; ``prefill``: ``lengths`` holds
    the prompt's length, logits for its last token only."""
    m = dims(cfg)
    L, D, H, KV, hd, F, V = (m[k] for k in ("L", "D", "H", "KV", "hd",
                                            "F", "V"))
    per_token = 2.0 * L * (D * (H + 2 * KV) * hd + H * hd * D + 3 * D * F)
    head = 2.0 * V * D
    if kind == "decode":
        return sum(per_token + head + 4.0 * L * H * hd * c for c in lengths)
    (P,) = lengths
    return P * per_token + head + 4.0 * L * H * hd * P * (P + 1) / 2.0
