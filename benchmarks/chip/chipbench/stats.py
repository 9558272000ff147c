"""Percentiles and the end-to-end arithmetic over request records.

Every request due in the window counts. One that failed or never finished
counts as infinitely late in the latency percentiles, so a tail cannot
improve by losing requests. Tokens delivered after the window closed (the
drain) do not count towards the rate.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field


@dataclass
class Record:
    """What the harness saw of one request, on the host's clock
    (``time.perf_counter`` seconds)."""
    rid: int
    tenant: int
    prompt_len: int
    max_new: int
    due: float
    prompt: list = field(default_factory=list)
    submitted: float = math.nan
    admitted: float = math.nan     # start of the step that prefilled it
    token_times: list = field(default_factory=list)
    tokens: list = field(default_factory=list)
    failed: bool = False

    @property
    def finished(self) -> bool:
        return not self.failed and len(self.tokens) == self.max_new


def percentile(values, p: float) -> float:
    """Linear interpolation between closest ranks (numpy's default);
    ``inf`` sorts last and stays ``inf`` where it is touched."""
    v = sorted(values)
    if not v:
        return math.nan
    rank = p / 100.0 * (len(v) - 1)
    lo, hi = math.floor(rank), math.ceil(rank)
    if lo == hi or v[hi] == v[lo]:
        return float(v[lo])
    if math.isinf(v[hi]):
        return math.inf
    return float(v[lo] + (v[hi] - v[lo]) * (rank - lo))


def ttft_s(r: Record) -> float:
    if r.failed or not r.token_times:
        return math.inf
    return r.token_times[0] - r.due


def gaps_s(records) -> list:
    out = []
    for r in records:
        t = r.token_times
        out.extend(b - a for a, b in zip(t, t[1:]))
    return out


def tokens_in(records, t0: float, t1: float) -> int:
    return sum(1 for r in records for t in r.token_times if t0 <= t < t1)


def end_to_end(records, t0: float, seconds: float) -> dict:
    """The end-to-end numbers of one window (before units and names)."""
    return {
        "ttft_p90_ms": percentile([ttft_s(r) for r in records], 90) * 1e3,
        "itl_p95_ms": percentile(gaps_s(records), 95) * 1e3,
        "tokens_per_s": tokens_in(records, t0, t0 + seconds) / seconds,
    }
