"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import _kv_block, decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_scan

KEY = jax.random.PRNGKey(7)


def tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,Hq,Hkv,hd,causal,window", [
    (2, 128, 4, 2, 64, True, None),
    (1, 256, 4, 1, 32, True, 64),
    (2, 100, 8, 8, 16, True, None),      # ragged S (padding path)
    (1, 64, 4, 4, 128, False, None),     # non-causal
    (1, 64, 16, 2, 8, True, 16),         # deep GQA + window
])
def test_flash_attention(B, S, Hq, Hkv, hd, causal, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd), jnp.float32).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          interpret=True, block_q=32, block_k=32)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,Hq,Hkv,hd,window", [
    (2, 256, 4, 2, 64, None),
    (3, 100, 8, 1, 32, None),            # ragged S
    (2, 512, 4, 4, 128, 128),            # MHA + window
    (1, 64, 16, 2, 16, None),
])
def test_decode_attention(B, S, Hq, Hkv, hd, window, dtype):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, Hq, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd), jnp.float32).astype(dtype)
    lengths = jax.random.randint(ks[3], (B,), 1, S + 1)
    out = decode_attention(q, k, v, lengths, window=window, interpret=True,
                           block_k=64)
    want = ref.decode_attention_ref(q, k, v, lengths, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


# a row's length against a 256-position cache read in 64-key blocks: free
# (0), one key, a block boundary and one past it, the whole cache, and past
# it (the kernel reads the whole cache, never a block beyond it)
LEN_S, LEN_BK = 256, 64


@pytest.mark.parametrize("window", [None, 96])
@pytest.mark.parametrize("length", [0, 1, LEN_BK, LEN_BK + 1, LEN_S,
                                    LEN_S + 300])
def test_decode_attention_row_lengths(length, window):
    ks = jax.random.split(KEY, 3)
    B, Hq, Hkv, hd = 3, 4, 2, 64
    q = jax.random.normal(ks[0], (B, Hq, hd))
    k = jax.random.normal(ks[1], (B, LEN_S, Hkv, hd))
    v = jax.random.normal(ks[2], (B, LEN_S, Hkv, hd))
    lengths = jnp.array([length, 37, 200], jnp.int32)
    out = decode_attention(q, k, v, lengths, window=window, interpret=True,
                           block_k=LEN_BK)
    want = ref.decode_attention_ref(q, k, v, jnp.minimum(lengths, LEN_S),
                                    window=window)
    np.testing.assert_allclose(out, want, **tol(jnp.float32))
    assert np.isfinite(np.asarray(out)).all()
    if length == 0:
        assert not np.asarray(out[0]).any()


@pytest.mark.parametrize("window", [None, 96])
def test_decode_attention_fetches_only_live_blocks(window):
    """The K/V index maps name each row's live blocks in order and repeat
    the last one, and the pipeline copies a block only when the named
    block changes: a row of length L costs the blocks holding its window
    of keys and a free row one block. No length, even one past the cache
    (the kernel clips those to it), names a block outside the cache."""
    nk = LEN_S // LEN_BK
    win = jnp.array([0 if window is None else window], jnp.int32)

    def named(length):
        lens = jnp.array([length], jnp.int32)
        return [int(_kv_block(0, j, lens, win, bk=LEN_BK, nk=nk,
                              windowed=window is not None)[1])
                for j in range(nk)]

    for length in [0, 1, 63, 64, 65, 150, 200, 255, 256]:
        got = named(length)
        first = max(length - window, 0) // LEN_BK if window else 0
        live = list(range(first, (length - 1) // LEN_BK + 1)) or [0]
        assert got == sorted(got), (length, got)
        assert sorted(set(got)) == live, (length, got)
    for length in [257, 556, 2**31 - 1]:
        assert all(0 <= i < nk for i in named(length)), length


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,P,N,chunk,init", [
    (2, 64, 4, 16, 16, 16, False),
    (1, 100, 2, 32, 64, 32, True),       # ragged + init state
    (2, 33, 4, 64, 32, 8, False),
])
def test_ssd_scan(B, S, H, P, N, chunk, init, dtype):
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (B, S, H, P)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = (jax.random.normal(ks[3], (B, S, N)) * 0.5).astype(dtype)
    Cm = (jax.random.normal(ks[4], (B, S, N)) * 0.5).astype(dtype)
    s0 = jax.random.normal(ks[5], (B, H, P, N)) if init else None
    y, sf = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, init_state=s0,
                     return_state=True, interpret=True)
    yr, sr = ref.ssd_scan_ref(x, dt, A, Bm, Cm, chunk=chunk, init_state=s0,
                              return_state=True)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               **tol(dtype))
    np.testing.assert_allclose(sf, sr, atol=1e-3, rtol=1e-3)


def test_ssd_matches_sequential_recurrence():
    """Chunked SSD == the literal state-space recurrence definition."""
    ks = jax.random.split(KEY, 6)
    B, S, H, P, N = 2, 48, 3, 8, 16
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, N)) * 0.5
    st = jnp.zeros((B, H, P, N))
    ys = []
    for t in range(S):
        decay = jnp.exp(dt[:, t] * A[None, :])
        st = st * decay[..., None, None] + jnp.einsum(
            "bh,bhp,bn->bhpn", dt[:, t], x[:, t], Bm[:, t])
        ys.append(jnp.einsum("bhpn,bn->bhp", st, Cm[:, t]))
    want = jnp.stack(ys, 1)
    got, sf = ref.ssd_scan_ref(x, dt, A, Bm, Cm, chunk=16, return_state=True)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(sf, st, atol=1e-4, rtol=1e-4)


def test_ssd_decode_matches_scan_tail():
    """One ssd_decode step == extending the scan by one token."""
    ks = jax.random.split(KEY, 6)
    B, S, H, P, N = 2, 17, 2, 8, 8
    x = jax.random.normal(ks[0], (B, S + 1, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S + 1, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S + 1, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S + 1, N)) * 0.5
    y_full = ref.ssd_scan_ref(x, dt, A, Bm, Cm, chunk=8)
    _, state = ref.ssd_scan_ref(x[:, :S], dt[:, :S], A, Bm[:, :S],
                                Cm[:, :S], chunk=8, return_state=True)
    y1, _ = ref.ssd_decode_ref(x[:, S], dt[:, S], A, Bm[:, S], Cm[:, S],
                               state)
    np.testing.assert_allclose(y1, y_full[:, S], atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("shape", [(8, 64), (2, 17, 128), (100, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(shape, dtype):
    x = jax.random.normal(KEY, shape, jnp.float32).astype(dtype)
    w = jax.random.normal(KEY, shape[-1:]) * 0.1
    out = rmsnorm(x, w, interpret=True, block_rows=16)
    want = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)
