"""Pure-jnp oracles for every Pallas kernel.

These are the numerical ground truth the kernels are validated against
(``tests/test_kernels.py``), and also the path used on non-TPU backends and
in the dry-run (so XLA cost analysis sees real FLOPs, not an opaque
callback).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------
def rmsnorm_ref(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(dtype)


# ---------------------------------------------------------------------------
# Flash attention (train / prefill): GQA + causal + optional sliding window
# ---------------------------------------------------------------------------
def flash_attention_ref(
    q: jax.Array,          # (B, S, Hq, hd)
    k: jax.Array,          # (B, S, Hkv, hd)
    v: jax.Array,          # (B, S, Hkv, hd)
    *,
    causal: bool = True,
    window: int | None = None,   # sliding window size (keys within [i-w+1, i])
    scale: float | None = None,
) -> jax.Array:
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    scale = scale if scale is not None else hd ** -0.5

    # grouped-query einsum: never materialize repeated KV heads or fp32
    # copies of K/V (fp32 accumulation via preferred_element_type)
    qg = (q * jnp.asarray(scale, q.dtype)).reshape(B, S, Hkv, group, hd)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32)
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, S, Hq, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode attention: one query token against a (possibly partial) KV cache
# ---------------------------------------------------------------------------
def decode_attention_ref(
    q: jax.Array,          # (B, Hq, hd)
    k_cache: jax.Array,    # (B, S, Hkv, hd)
    v_cache: jax.Array,    # (B, S, Hkv, hd)
    lengths: jax.Array,    # (B,) int32 — number of valid cache entries
    *,
    window: int | None = None,
    scale: float | None = None,
) -> jax.Array:
    B, Hq, hd = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    group = Hq // Hkv
    scale = scale if scale is not None else hd ** -0.5

    # grouped-query einsum against the cache in its native dtype — never
    # materialize repeated KV heads or an fp32 cache copy
    qg = (q * jnp.asarray(scale, q.dtype)).reshape(B, Hkv, group, hd)
    logits = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                        preferred_element_type=jnp.float32)
    kpos = jnp.arange(S)[None, :]                       # (1, S)
    valid = kpos < lengths[:, None]                     # (B, S)
    if window is not None:
        valid &= kpos > (lengths[:, None] - 1 - window)
    valid = valid[:, None, None, :]
    logits = jnp.where(valid, logits, NEG_INF)
    # a row with no valid key (length 0: a free slot) attends nothing
    p = jnp.where(valid, jax.nn.softmax(logits, axis=-1), 0.0)
    out = jnp.einsum("bhgk,bkhd->bhgd", p.astype(q.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Hq, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Mamba2 SSD chunked scan (state-space duality)
# ---------------------------------------------------------------------------
def _segsum(a: jax.Array) -> jax.Array:
    """Stable segment-sum: out[..., i, j] = sum_{k=j+1..i} a[..., k] for j<=i."""
    L = a.shape[-1]
    cum = jnp.cumsum(a, axis=-1)
    seg = cum[..., :, None] - cum[..., None, :]
    mask = jnp.tril(jnp.ones((L, L), dtype=bool), k=0)
    return jnp.where(mask, seg, -jnp.inf)


def ssd_scan_ref(
    x: jax.Array,        # (B, S, H, P)  — inputs per head
    dt: jax.Array,       # (B, S, H)     — softplus-activated step sizes
    A: jax.Array,        # (H,)          — negative decay rates
    Bm: jax.Array,       # (B, S, N)     — input matrix (single group)
    Cm: jax.Array,       # (B, S, N)     — output matrix (single group)
    *,
    chunk: int = 64,
    init_state: jax.Array | None = None,   # (B, H, P, N)
    return_state: bool = False,
):
    """Chunked SSD computation (Mamba2, arXiv:2405.21060 listing 1).

    y[t] = C[t] . state[t],  state[t] = exp(dt[t]*A) * state[t-1]
                                        + dt[t] * B[t] (outer) x[t]
    """
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    if S % chunk:
        # pad with dt=0 tokens: decay=exp(0)=1 and contribution dt*Bx=0, so
        # the final state is unchanged and padded outputs are discarded.
        pad = chunk - S % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
        out = ssd_scan_ref(x, dt, A, Bm, Cm, chunk=chunk,
                           init_state=init_state, return_state=return_state)
        if return_state:
            return out[0][:, :S], out[1]
        return out[:, :S]
    nc = S // chunk

    f32 = jnp.float32
    x_ = x.astype(f32).reshape(Bsz, nc, chunk, H, P)
    dt_ = dt.astype(f32).reshape(Bsz, nc, chunk, H)
    B_ = Bm.astype(f32).reshape(Bsz, nc, chunk, N)
    C_ = Cm.astype(f32).reshape(Bsz, nc, chunk, N)

    a = dt_ * A.astype(f32)[None, None, None, :]        # (b,c,l,h) log-decay
    a = jnp.moveaxis(a, -1, -2)                         # (b,c,h,l)
    a_cs = jnp.cumsum(a, axis=-1)                       # (b,c,h,l)

    # 1. intra-chunk (diagonal block) output
    L = jnp.exp(_segsum(a))                             # (b,c,h,l,l)
    Y_diag = jnp.einsum("bcln,bcsn,bchls,bcshp->bclhp", C_, B_, L, dt_[..., None] * x_)

    # 2. per-chunk final states
    decay_states = jnp.exp(a_cs[..., -1:] - a_cs)       # (b,c,h,l)
    states = jnp.einsum("bcln,bchl,bclhp->bchpn", B_, decay_states, dt_[..., None] * x_)

    # 3. inter-chunk recurrence (scan over chunks)
    chunk_decay = jnp.exp(a_cs[..., -1])                # (b,c,h)
    s0 = (jnp.zeros((Bsz, H, P, N), f32) if init_state is None
          else init_state.astype(f32))

    def step(carry, inp):
        st_new, decay = inp                             # (b,h,p,n), (b,h)
        prev = carry
        cur = prev * decay[..., None, None] + st_new
        return cur, prev

    chunk_states = jnp.moveaxis(states, 1, 0)           # (c,b,h,p,n)
    chunk_decays = jnp.moveaxis(chunk_decay, 1, 0)      # (c,b,h)
    final, prevs = jax.lax.scan(step, s0, (chunk_states, chunk_decays))
    prev_states = jnp.moveaxis(prevs, 0, 1)             # (b,c,h,p,n)

    # 4. state -> output contribution
    state_decay = jnp.exp(a_cs)                         # (b,c,h,l)
    Y_off = jnp.einsum("bcln,bchpn,bchl->bclhp", C_, prev_states, state_decay)

    y = (Y_diag + Y_off).reshape(Bsz, S, H, P).astype(x.dtype)
    if return_state:
        return y, final.astype(f32)
    return y


def ssd_decode_ref(
    x: jax.Array,        # (B, H, P) — single-token input
    dt: jax.Array,       # (B, H)
    A: jax.Array,        # (H,)
    Bm: jax.Array,       # (B, N)
    Cm: jax.Array,       # (B, N)
    state: jax.Array,    # (B, H, P, N) fp32
):
    """Single-token SSD state update + output."""
    f32 = jnp.float32
    xf, dtf = x.astype(f32), dt.astype(f32)
    decay = jnp.exp(dtf * A.astype(f32)[None, :])       # (B, H)
    dBx = jnp.einsum("bh,bhp,bn->bhpn", dtf, xf, Bm.astype(f32))
    new_state = state * decay[..., None, None] + dBx
    y = jnp.einsum("bhpn,bn->bhp", new_state, Cm.astype(f32))
    return y.astype(x.dtype), new_state
