"""Plain float32 reference of a Mamba2 language model (state-spaces
``MambaLMHeadModel`` with Mamba2 mixers, arXiv:2405.21060), from the
paper's recurrence and the published ``config.json`` with the ``Mamba2``
layer's own defaults. It imports nothing of the program under test, and
makes its weights itself from the seed.

Block: x = rmsnorm(h); [z, xBC, dt] = x W_in; xBC = silu(causal depthwise
conv(xBC) + b); dt = softplus(dt + dt_bias); A = -exp(A_log); per head the
state runs s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T and y_t = s_t C_t +
D x_t, token by token (no chunking); y = rmsnorm(y * silu(z)); h += y W_out.
One B/C group; tied embeddings.

One departure from the checkpoint's parameterisation, shared with the
program's parameter tree: each RMSNorm stores its weight as an offset from
one (scale = 1 + w). Everything here is float32, the residual stream
included.

``precision="fp8"`` is the control: the projections and the head take
float8 e4m3 inputs and weights; the recurrence stays float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench import costs
from chipbench.numerics import linear, rmsnorm


def dims(cfg: dict) -> dict:
    c = cfg["config"]
    d = {**cfg["layer_defaults"]}
    D = c["d_model"]
    din = d["expand"] * D
    mult = c["pad_vocab_size_multiple"]
    return {"L": c["n_layer"], "D": D, "din": din, "N": d["d_state"],
            "P": d["headdim"], "H": din // d["headdim"], "K": d["d_conv"],
            "V": math.ceil(c["vocab_size"] / mult) * mult,
            "eps": d["norm_epsilon"]}


def layout(cfg: dict) -> dict:
    m = dims(cfg)
    L, D, din, N, H, K, V = (m[k] for k in ("L", "D", "din", "N", "H",
                                            "K", "V"))
    cd = din + 2 * N

    def st(shape, init):
        return {"shape": [L] + shape, "stacked": True, "init": init}

    norm = {"kind": "sym", "std": 0.05}
    conv = {"kind": "range", "lo": -K ** -0.5, "hi": K ** -0.5}
    return {
        "embed/tok": {"shape": [V, D], "init": {"kind": "sym",
                                                 "std": 0.02}},
        "layers/ln1": st([D], norm),
        "layers/ssm/in_proj": st([D, 2 * din + 2 * N + H],
                                 {"kind": "sym", "std": D ** -0.5}),
        "layers/ssm/conv_w": st([K, cd], conv),
        "layers/ssm/conv_b": st([cd], conv),
        # A in [-16, -1] and dt in [1e-3, 1e-1], the layer's init ranges
        "layers/ssm/A_log": st([H], {"kind": "range", "lo": 0.0,
                                     "hi": math.log(16.0)}),
        "layers/ssm/D": st([H], {"kind": "const", "value": 1.0}),
        "layers/ssm/dt_bias": st([H], {"kind": "range", "lo": -6.907,
                                       "hi": -2.252}),
        "layers/ssm/norm": st([din], norm),
        "layers/ssm/out_proj": st([din, D], {"kind": "sym",
                                             "std": (din * L) ** -0.5}),
        "final_norm": {"shape": [D], "init": norm},
    }


@functools.partial(jax.jit, static_argnames=("dims_", "precision"))
def _layer(W, h, *, dims_, precision):
    m = dict(dims_)
    B, T, _ = h.shape
    din, N, H, P, K = m["din"], m["N"], m["H"], m["P"], m["K"]
    x = rmsnorm(h, W["layers/ln1"], m["eps"])
    zxbcdt = linear(x, W["layers/ssm/in_proj"], precision)
    z = zxbcdt[..., :din]
    xbc = zxbcdt[..., din:2 * din + 2 * N]
    dt = zxbcdt[..., 2 * din + 2 * N:]
    xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(xp[:, i:i + T] * W["layers/ssm/conv_w"][i] for i in range(K))
    xbc = jax.nn.silu(conv + W["layers/ssm/conv_b"])
    xs = xbc[..., :din].reshape(B, T, H, P)
    Bm, Cm = xbc[..., din:din + N], xbc[..., din + N:]
    dt = jax.nn.softplus(dt + W["layers/ssm/dt_bias"])
    A = -jnp.exp(W["layers/ssm/A_log"])

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = (jnp.exp(dt_t * A)[:, :, None, None] * s
             + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, None, None, :])
        return s, jnp.einsum("bhpn,bn->bhp", s, c_t)

    s0 = jnp.zeros((B, H, P, N), jnp.float32)
    seq = (jnp.moveaxis(xs, 1, 0), jnp.moveaxis(dt, 1, 0),
           jnp.moveaxis(Bm, 1, 0), jnp.moveaxis(Cm, 1, 0))
    _, y = jax.lax.scan(step, s0, seq)
    y = jnp.moveaxis(y, 0, 1) + W["layers/ssm/D"][:, None] * xs
    y = y.reshape(B, T, din) * jax.nn.silu(z)
    y = rmsnorm(y, W["layers/ssm/norm"], m["eps"])
    return h + linear(y, W["layers/ssm/out_proj"], precision)


def final_hidden(cfg: dict, tokens, layer_weights, leaf, precision: str):
    """tokens (B, T) int32 -> the final normed hidden state (B, T, D)."""
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
    slot (``lengths`` is not needed: the state has a fixed size);
    ``prefill``: ``lengths`` holds the prompt's length, logits for its
    last token only, the scan by chunks as ``costs.ssd_scan`` counts it."""
    m = dims(cfg)
    L, D, din, N, H, P, K, V = (m[k] for k in ("L", "D", "din", "N", "H",
                                               "P", "K", "V"))
    in_dim = 2 * din + 2 * N + H
    per_token = L * (2.0 * D * in_dim + 2.0 * din * D
                     + 2.0 * K * (din + 2 * N))
    head = 2.0 * V * D
    if kind == "decode":
        return len(lengths) * (per_token + head + L * 5.0 * H * P * N)
    (S,) = lengths
    chunk = cfg["layer_defaults"]["chunk_size"]
    scan, _ = costs.ssd_scan(S, H, P, N, chunk)
    return S * per_token + head + L * scan
