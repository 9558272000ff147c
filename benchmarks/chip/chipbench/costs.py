"""Operations and bytes that a kernel call needs, from its shapes.

These count what the computation requires, not what an implementation
happens to do: attention over each slot's valid keys only, causal pairs
only, a shared matrix product once. A roofline share built on them is the
least time the chip could take over the time the kernel took, so it falls
when a kernel reads padding or recomputes. Bytes are those that must
cross HBM once: inputs read, outputs written.
"""
from __future__ import annotations


def least_time_s(flops: float, nbytes: float, peak: dict) -> tuple:
    """(seconds, bound) of the roofline for one call."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def decode_attention(ctx: list, Hq: int, Hkv: int, hd: int,
                     itemsize: int = 2) -> tuple:
    """One token per slot against ``ctx[i]`` valid cached keys."""
    keys = sum(ctx)
    flops = 4.0 * Hq * hd * keys
    nbytes = (2.0 * keys * Hkv * hd + 2.0 * len(ctx) * Hq * hd) * itemsize
    return flops, nbytes


def flash_attention(P: int, Hq: int, Hkv: int, hd: int,
                    itemsize: int = 2) -> tuple:
    """Causal self-attention of one prompt of P tokens."""
    pairs = P * (P + 1) / 2.0
    flops = 4.0 * Hq * hd * pairs
    nbytes = (2.0 * Hq + 2.0 * Hkv) * P * hd * itemsize
    return flops, nbytes


def ssd_scan(S: int, H: int, P: int, N: int, chunk: int,
             itemsize: int = 2) -> tuple:
    """Chunked SSD of one sequence of S tokens (one B/C group): per chunk
    of c tokens C.B^T once, and per head the masked (c x c) product, the
    state's contribution and the state's update."""
    flops = 0.0
    for c0 in range(0, S, chunk):
        c = min(chunk, S - c0)
        tri = c * (c + 1) / 2.0
        flops += 2.0 * tri * N + H * (2.0 * tri * P + 4.0 * c * N * P)
    nbytes = (2.0 * S * H * P * itemsize      # x in, y out
              + S * H * 4.0                   # dt, float32
              + 2.0 * S * N * itemsize        # B, C
              + H * 4.0                       # A
              + 2.0 * H * P * N * 4.0)        # state in and out, float32
    return flops, nbytes

