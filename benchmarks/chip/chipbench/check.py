"""Whether what the timed path served is correct.

After the window has closed and the program's state is freed, a sample of
the requests it finished, drawn from the seed and holding the longest, is
run once through the configuration's plain float32 reference: each
prompt followed by the tokens served for it. At every position where a
token was served, the gap is the reference's best logit minus its logit
for the served token (0 where they agree). The widest gap over the sample
is compared with the configuration's limit. Decoding is greedy, so a
sound program's gaps are rounding; a token altered, a slot's tokens given
to another request, a cache not written, or another tenant's weights show
as gaps of a logit or more.

The control computes the same positions in float8 (``precision="fp8"``)
and reads the reference's gap of the token that it puts first.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights
from chipbench.numerics import linear

ROW_BLOCK = 256
SEQ_BUCKET = 256
# served tokens a run compares at the least: some hundreds, also where a
# mix's outputs are a few tokens each
MIN_TOKENS = 320


def sample(records, n_tenants: int, per_tenant: int, seed: int,
           min_tokens: int = 0) -> dict:
    """{tenant: [records]}: ``per_tenant`` finished requests of each
    tenant, drawn from the seed, and more where a tenant's sample serves
    fewer than its share of ``min_tokens`` tokens (a mix of short
    outputs); the longest finished request is in it."""
    done = [r for r in records if r.finished]
    if not done:
        return {}
    longest = max(done, key=lambda r: (r.prompt_len + r.max_new, -r.rid))
    words = [seed & 0xFFFFFFFF, seed >> 32]
    rng = np.random.default_rng(words + [0xC4EC])
    more = np.random.default_rng(words + [0xC4ED])   # leaves rng's draw be
    need = min_tokens / n_tenants
    out = {}
    for t in range(n_tenants):
        pool = [r for r in done if r.tenant == t and r is not longest]
        take = [longest] if longest.tenant == t else []
        n = min(per_tenant - len(take), len(pool))
        idx = rng.choice(len(pool), size=n, replace=False) if n > 0 else []
        take += [pool[i] for i in sorted(idx)]
        rest = [r for i, r in enumerate(pool) if i not in set(idx)]
        if rest and sum(len(r.tokens) for r in take) < need:
            for i in more.permutation(len(rest)):
                if sum(len(r.tokens) for r in take) >= need:
                    break
                take.append(rest[i])
        if take:
            out[t] = take
    return out


@jax.jit
def _gaps(rows, E, idx):
    """max(logits) - logits[idx] per row, float32 logits rows @ E^T."""
    with jax.default_matmul_precision("highest"):
        logits = rows @ E.T
    best = jnp.max(logits, axis=-1)
    return best - jnp.take_along_axis(logits, idx[:, None], axis=-1)[:, 0]


def _argmax(rows, E, precision):
    with jax.default_matmul_precision("highest"):
        return jnp.argmax(linear(rows, E.T, precision), axis=-1)


_argmax_jit = jax.jit(_argmax, static_argnames=("precision",))


def _pad_rows(rows: np.ndarray) -> np.ndarray:
    return np.pad(rows, ((0, ROW_BLOCK - rows.shape[0]), (0, 0)))


def tenant_gaps(cell, fns: tuple, seed: int, tenant: int, reqs: list,
                precisions=("f32",)) -> dict:
    """{precision: [per-request arrays of gaps]} for one tenant's sampled
    requests. "f32" is the served tokens' gaps; any other precision is a
    control: the gaps of the tokens it ranks first. ``fns`` holds
    ``weights.layer_fn`` and ``weights.flat_fn`` of the layout."""
    cfg, ref = cell.config, cell.reference
    layer_fn, flat_fn = fns
    words = weights.seed_words(seed, tenant)
    leaves: dict = {}

    def leaf(path):
        if path not in leaves:
            leaves[path] = flat_fn(words, path)
        return leaves[path]

    seqs = [r.prompt + r.tokens[:-1] for r in reqs]
    T = SEQ_BUCKET * math.ceil(max(len(s) for s in seqs) / SEQ_BUCKET)
    tok = np.zeros((len(seqs), T), np.int32)
    for i, s in enumerate(seqs):
        tok[i, :len(s)] = s
    tok = jnp.asarray(tok)
    E = ref.head(cfg, leaf)
    hidden = {p: np.asarray(ref.final_hidden(
        cfg, tok, lambda l: layer_fn(words, jnp.uint32(l)), leaf, p))
        for p in precisions}
    out: dict = {p: [] for p in precisions}
    for i, r in enumerate(reqs):
        lo, n = r.prompt_len - 1, len(r.tokens)
        served = np.asarray(r.tokens, np.int32)
        for p in precisions:
            gaps = []
            for j in range(0, n, ROW_BLOCK):
                m = min(ROW_BLOCK, n - j)
                rows = _pad_rows(hidden["f32"][i, lo + j:lo + j + m])
                if p == "f32":
                    idx = served[j:j + m]
                else:
                    rows_p = _pad_rows(hidden[p][i, lo + j:lo + j + m])
                    idx = np.asarray(_argmax_jit(rows_p, E, precision=p))[:m]
                idx = np.pad(idx, (0, ROW_BLOCK - m)).astype(np.int32)
                gaps.append(np.asarray(_gaps(rows, E, idx))[:m])
            out[p].append(np.concatenate(gaps))
    return out


def gap_limit(cfg: dict) -> float:
    """The configuration's limit; one without readings has none, and is
    refused before any set-up."""
    limit = cfg["check"]["gap_limit"]
    if limit is None:
        raise ValueError(f"configuration {cfg['name']} has no gap_limit: "
                         f"read one with limits.py first")
    return limit


def verdict(cfg: dict, gap: float, unfinished: int, oov: int,
            tenants: int) -> tuple:
    """(correct, checks): the gate a run passes, and each number it
    compared beside its limit. ``gap`` is the widest gap of the tokens
    judged: the served ones, or a control's first-ranked ones."""
    limit = gap_limit(cfg)
    checks = {
        "gap_max": {"value": gap if math.isfinite(gap) else None,
                    "limit": limit},
        "unfinished": {"value": unfinished, "limit": 0},
        "out_of_vocab": {"value": oov, "limit": 0},
        "checked_tenants": {"value": tenants, "limit": cfg["tenants"]},
    }
    correct = (math.isfinite(gap) and gap <= limit and unfinished == 0
               and oov == 0 and tenants == cfg["tenants"])
    return bool(correct), checks


def compare(cell, seed: int, records, precisions=("f32",)) -> dict:
    """The numbers ``correct`` is decided on, and the sample's size."""
    cfg = cell.config
    picked = sample(records, cfg["tenants"], cfg["check"]["per_tenant"],
                    seed, MIN_TOKENS)
    layout = cell.reference.layout(cfg)
    fns = (weights.layer_fn(layout), weights.flat_fn(layout))
    widest = {p: 0.0 for p in precisions}
    positions = 0
    for t, reqs in sorted(picked.items()):
        res = tenant_gaps(cell, fns, seed, t, reqs, precisions)
        for p, per_req in res.items():
            for g in per_req:
                widest[p] = max(widest[p], float(np.max(g)))
        positions += sum(len(r.tokens) for r in reqs)
    return {"widest": widest, "requests": sum(map(len, picked.values())),
            "positions": positions, "tenants": len(picked)}


def judge(cell, seed: int, records, precisions=("f32",)) -> tuple:
    """({precision: (correct, checks)}, comparison): the gate applied to
    a window's records, once with the served tokens' widest gap ("f32")
    and once for each control with the widest gap of the tokens that it
    ranks first in their place. The comparison is None where a served
    token lies outside the vocabulary."""
    cfg = cell.config
    vocab = cell.reference.dims(cfg)["V"]
    unfinished = sum(1 for r in records if not r.finished)
    oov = sum(1 for r in records for x in r.tokens if not 0 <= x < vocab)
    cmp = compare(cell, seed, records, precisions) if oov == 0 else None
    tenants = cmp["tenants"] if cmp else 0
    return {p: verdict(cfg, cmp["widest"][p] if cmp else math.inf,
                       unfinished, oov, tenants)
            for p in precisions}, cmp
