"""Which Pallas kernel a device operation is, read from its HLO text.

The TPU trace names a Pallas kernel only as a ``custom-call`` with target
``tpu_custom_call``; the kernel's own name is not recorded. Each kernel is
therefore recognised by its operands and results, as the program's
kernels lay them out:

- ``decode_attention``: (s32[B] lengths, s32[1] window, q[B,Hq,hd],
  k[B,S,Hkv,hd], v[B,S,Hkv,hd]) -> [B,Hq,hd]
- ``flash_attention``: (s32[1] window, q[B,Hq,S,hd], k[B,Hkv,S,hd],
  v[B,Hkv,S,hd]) -> [B,Hq,S,hd]
- ``ssd_scan``: (x[B,H,S,P], dt, dt, A f32[H], B[B,S,N], C[B,S,N],
  state f32[B,H,P,N]) -> (y[B,H,S,P], state f32[B,H,P,N])
- ``rmsnorm``: (x[R,D], w[D]) -> [R,D]

A kernel whose layout changes is no longer recognised, and the metrics
that read it fall silent rather than read another kernel.
"""
from __future__ import annotations

import re

_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16|f8e4m3fn|f8e5m2)\[([0-9,]*)\]")
_CALL = " custom-call("


def _shapes(text: str) -> list:
    return [(d, tuple(int(x) for x in s.split(",") if x))
            for d, s in _SHAPE.findall(text)]


def signature(op: str):
    """(results, operands) of a ``tpu_custom_call`` op, each a list of
    (dtype, shape); None for any other op."""
    if 'custom_call_target="tpu_custom_call"' not in op or _CALL not in op:
        return None
    left, right = op.split(_CALL, 1)
    operands = right.split("), custom_call_target", 1)[0]
    results = left.split("=", 1)[1] if "=" in left else left
    return _shapes(results), _shapes(operands)


def kernel_of(op: str):
    sig = signature(op)
    if sig is None:
        return None
    res, ops = sig
    ranks = [len(s) for _, s in ops]
    if (len(ops) == 5 and ops[0][0] == "s32" and ranks[:5] == [1, 1, 3, 4, 4]
            and ops[1][1] == (1,)):
        return "decode_attention"
    if (len(ops) == 4 and ops[0] == ("s32", (1,))
            and ranks[1:] == [4, 4, 4]):
        return "flash_attention"
    if (len(ops) == 7 and len(res) == 2 and res[1][0] == "f32"
            and [len(s) for _, s in res] == [4, 4]):
        return "ssd_scan"
    if len(ops) == 2 and ranks == [2, 1] and len(res) == 1:
        return "rmsnorm"
    return "other"


def shapes(op: str) -> dict:
    """The named dimensions of a recognised kernel op."""
    res, ops = signature(op)
    k = kernel_of(op)
    if k == "decode_attention":
        B, Hq, hd = ops[2][1]
        _, S, Hkv, _ = ops[3][1]
        return {"B": B, "Hq": Hq, "hd": hd, "S": S, "Hkv": Hkv}
    if k == "flash_attention":
        B, Hq, S, hd = ops[1][1]
        return {"B": B, "Hq": Hq, "S": S, "hd": hd, "Hkv": ops[2][1][1]}
    if k == "ssd_scan":
        B, H, S, P = ops[0][1]
        return {"B": B, "H": H, "S": S, "P": P, "N": ops[4][1][2]}
    raise ValueError(f"no shapes for kernel {k!r}")
