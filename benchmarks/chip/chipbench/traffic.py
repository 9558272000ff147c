"""One general generator for every traffic mix.

A mix is a JSON file under ``benchmarks/chip/traffic/`` (parameters only:
rates, bursts, tenant shares, length distributions). Every seed gets the
same work: the same number of requests in each phase, the same multiset of
inter-arrival gaps, tenants, prompt lengths and output lengths. The seed
only permutes them and draws the token ids. So two seeds differ in the
order and timing of the same requests, never in how much work there is.

Arrivals are open loop. A phase of ``seconds`` at ``rate_scale`` times the
mix's mean rate holds ``round(rate * seconds)`` requests; their gaps are
the exponential distribution's quantiles, permuted, and scaled so that the
phase holds them exactly. Phases repeat from the start of the window.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist

import numpy as np


@dataclass
class Request:
    rid: int
    due_s: float          # seconds after the window opens
    tenant: int
    prompt: list          # token ids
    max_new: int


@dataclass
class Schedule:
    requests: list = field(default_factory=list)

    @property
    def prompt_lengths(self) -> list:
        return sorted({len(r.prompt) for r in self.requests})


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lengths(spec: dict, n: int) -> np.ndarray:
    """n lengths at the distribution's quantiles, in increasing order."""
    q = _quantiles(n)
    kind = spec["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in q])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif kind == "uniform":
        lo, hi = spec["min"], spec["max"]
        vals = lo + q * (hi + 1 - lo)
        vals = np.floor(vals)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    vals = np.ceil(vals).astype(np.int64)
    if "min" in spec:
        vals = np.maximum(vals, spec["min"])
    if "max" in spec:
        vals = np.minimum(vals, spec["max"])
    buckets = spec.get("round_up_to")
    if buckets:
        b = np.array(sorted(buckets))
        idx = np.minimum(np.searchsorted(b, vals, side="left"), len(b) - 1)
        vals = b[idx]
    return vals


def tenant_shares(spec: dict, n_tenants: int) -> np.ndarray:
    if "shares" in spec:
        s = np.asarray(spec["shares"], float)
        if len(s) != n_tenants:
            raise ValueError(f"{len(s)} tenant shares for {n_tenants} "
                             f"tenants")
    else:
        s = 1.0 / np.arange(1, n_tenants + 1) ** spec["zipf_s"]
    return s / s.sum()


def _apportion(n: int, shares: np.ndarray) -> np.ndarray:
    """Largest-remainder split of n items by shares."""
    raw = shares * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return counts


def arrival_times(arrivals: dict, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    mean = arrivals["mean_rate_per_s"]
    phases = arrivals.get("phases") or [{"seconds": seconds,
                                         "rate_scale": 1.0}]
    times, t, i = [], 0.0, 0
    while t < seconds - 1e-9:
        ph = phases[i % len(phases)]
        span = min(ph["seconds"], seconds - t)
        n = int(round(mean * ph["rate_scale"] * span))
        if n:
            # n arrivals and one closing gap share the phase exactly
            gaps = -np.log1p(-_quantiles(n + 1))
            gaps = rng.permutation(gaps)
            gaps *= span / gaps.sum()
            times.extend(t + np.cumsum(gaps[:n]))
        t += span
        i += 1
    return np.asarray(times)


def generate(mix: dict, seconds: float, seed: int, n_tenants: int,
             vocab: int) -> Schedule:
    """The requests due in a window of ``seconds``, from ``seed``."""
    _check_mix(mix)
    rng = np.random.default_rng(seed)
    due = arrival_times(mix["arrivals"], seconds, rng)
    n = len(due)
    tenants = np.repeat(np.arange(n_tenants),
                        _apportion(n, tenant_shares(mix["tenants"],
                                                    n_tenants)))
    tenants = rng.permutation(tenants)
    plen = rng.permutation(_lengths(mix["prompt_len"], n))
    olen = rng.permutation(_lengths(mix["output_len"], n))
    reqs = []
    for i in range(n):
        prompt = rng.integers(0, vocab, int(plen[i])).tolist()
        reqs.append(Request(i, float(due[i]), int(tenants[i]), prompt,
                            int(olen[i])))
    return Schedule(reqs)


def longest_request(mix: dict) -> int:
    """Tokens of the longest request the mix can make: prompt plus
    output, the context a slot has to hold."""
    def top(spec):
        if spec.get("round_up_to"):
            return max(spec["round_up_to"])
        return spec["max"]
    return top(mix["prompt_len"]) + top(mix["output_len"])


def describe(s: Schedule, seconds: float) -> dict:
    if not s.requests:
        return {"requests": 0}
    p = np.array([len(r.prompt) for r in s.requests])
    o = np.array([r.max_new for r in s.requests])
    return {"requests": len(s.requests),
            "rate_per_s": len(s.requests) / seconds,
            "prompt_len_mean": float(p.mean()),
            "output_len_mean": float(o.mean()),
            "prompt_lengths": s.prompt_lengths,
            "tenants": np.bincount([r.tenant for r in s.requests]).tolist()}


def _check_mix(mix: dict) -> None:
    for key in ("arrivals", "tenants", "prompt_len", "output_len"):
        if key not in mix:
            raise ValueError(f"traffic mix lacks {key!r}")
    if not math.isfinite(mix["arrivals"]["mean_rate_per_s"]):
        raise ValueError("traffic mix rate is not finite")
