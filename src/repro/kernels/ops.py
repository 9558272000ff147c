"""jit'd public wrappers for the Pallas kernels.

Dispatch policy (``kernel_mode()``):
  * ``auto``      — compiled Pallas kernel on TPU, jnp reference elsewhere
                    (CPU dry-run must see real HLO FLOPs, not an opaque
                    callback).
  * ``interpret`` — Pallas kernel in interpret mode (CPU correctness tests).
  * ``ref``       — force the pure-jnp oracle (the float32 reference).

Only ``set_kernel_mode`` changes the mode: nothing in the environment can
route a TPU run to the reference.
"""
from __future__ import annotations

import jax

from repro.kernels import ref as _ref

_MODES = ("auto", "interpret", "ref")
_mode = "auto"


def set_kernel_mode(mode: str) -> None:
    global _mode
    if mode not in _MODES:
        raise ValueError(f"kernel mode {mode!r} not in {_MODES}")
    _mode = mode


def kernel_mode() -> str:
    return _mode


def _use_pallas() -> tuple[bool, bool]:
    """-> (use_kernel, interpret)"""
    if _mode == "interpret":
        return True, True
    if _mode == "ref":
        return False, False
    return jax.default_backend() == "tpu", False


# ---------------------------------------------------------------------------
def rmsnorm(x, w, eps: float = 1e-5):
    use, interp = _use_pallas()
    if use:
        from repro.kernels import rmsnorm as _k
        return _k.rmsnorm(x, w, eps=eps, interpret=interp)
    return _ref.rmsnorm_ref(x, w, eps)


def flash_attention(q, k, v, *, causal=True, window=None, scale=None):
    use, interp = _use_pallas()
    if use:
        from repro.kernels import flash_attention as _k
        return _k.flash_attention(
            q, k, v, causal=causal, window=window, scale=scale, interpret=interp)
    return _ref.flash_attention_ref(q, k, v, causal=causal, window=window, scale=scale)


def decode_attention(q, k_cache, v_cache, lengths, *, window=None, scale=None):
    use, interp = _use_pallas()
    if use:
        from repro.kernels import decode_attention as _k
        return _k.decode_attention(
            q, k_cache, v_cache, lengths, window=window, scale=scale, interpret=interp)
    return _ref.decode_attention_ref(
        q, k_cache, v_cache, lengths, window=window, scale=scale)


def ssd_scan(x, dt, A, Bm, Cm, *, chunk=64, init_state=None, return_state=False):
    use, interp = _use_pallas()
    if use:
        from repro.kernels import ssd_scan as _k
        return _k.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, init_state=init_state,
                           return_state=return_state, interpret=interp)
    return _ref.ssd_scan_ref(x, dt, A, Bm, Cm, chunk=chunk, init_state=init_state,
                             return_state=return_state)


def ssd_decode(x, dt, A, Bm, Cm, state):
    # Single-token state update is bandwidth-trivial; jnp path is used on all
    # backends (XLA fuses it into one pass).
    return _ref.ssd_decode_ref(x, dt, A, Bm, Cm, state)
