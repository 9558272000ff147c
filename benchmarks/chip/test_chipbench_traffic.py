"""Traffic generation: reproduced from a seed, the same work for every
seed, and the rates and length distributions the mix states."""
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from chipbench import traffic

BENCH = Path(__file__).resolve().parent

CHAT = {"arrivals": {"mean_rate_per_s": 3.0},
        "tenants": {"shares": [0.7, 0.3]},
        "prompt_len": {"dist": "lognormal", "median": 256, "sigma": 0.7,
                       "round_up_to": [128, 256, 512, 1024]},
        "output_len": {"dist": "lognormal", "median": 96, "sigma": 0.6,
                       "min": 16, "max": 256}}
BURST = {"arrivals": {"mean_rate_per_s": 4.0,
                      "phases": [{"seconds": 2, "rate_scale": 2.5},
                                 {"seconds": 6, "rate_scale": 0.5}]},
         "tenants": {"zipf_s": 1.0},
         "prompt_len": {"dist": "lognormal", "median": 128, "sigma": 0.5,
                        "round_up_to": [128, 256]},
         "output_len": {"dist": "lognormal", "median": 48, "sigma": 0.6,
                        "min": 8, "max": 128}}
BIG_SEED = 2 ** 31 + 977


def _key(s):
    return [(r.due_s, r.tenant, tuple(r.prompt), r.max_new)
            for r in s.requests]


@pytest.mark.parametrize("mix,tenants", [(CHAT, 2), (BURST, 6)])
def test_same_seed_same_schedule(mix, tenants):
    a = traffic.generate(mix, 40, BIG_SEED, tenants, 1000)
    b = traffic.generate(mix, 40, BIG_SEED, tenants, 1000)
    assert _key(a) == _key(b)


@pytest.mark.parametrize("mix,tenants", [(CHAT, 2), (BURST, 6)])
def test_seeds_permute_the_same_work(mix, tenants):
    a = traffic.generate(mix, 40, 11, tenants, 1000)
    b = traffic.generate(mix, 40, 12, tenants, 1000)
    assert _key(a) != _key(b)
    for get in (lambda r: len(r.prompt), lambda r: r.max_new,
                lambda r: r.tenant):
        assert (Counter(map(get, a.requests))
                == Counter(map(get, b.requests)))
    ga = sorted(np.diff([0.0] + [r.due_s for r in a.requests]))
    gb = sorted(np.diff([0.0] + [r.due_s for r in b.requests]))
    assert len(ga) == len(gb)


def test_steady_rate_and_window():
    s = traffic.generate(CHAT, 40, 5, 2, 1000)
    assert len(s.requests) == 120
    due = [r.due_s for r in s.requests]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 40


def test_bursts_hold_their_counts():
    s = traffic.generate(BURST, 40, 5, 6, 1000)
    due = np.array([r.due_s for r in s.requests])
    for start in range(0, 40, 8):
        assert ((due >= start) & (due < start + 2)).sum() == 20
        assert ((due >= start + 2) & (due < start + 8)).sum() == 12


def test_length_distributions():
    n = 2000
    p = traffic._lengths(CHAT["prompt_len"], n)
    assert set(p) <= {128, 256, 512, 1024}
    # lognormal(256, 0.7): P(X <= 128) = Phi(-ln2 / 0.7) = 0.161
    assert abs((p == 128).mean() - 0.161) < 0.01
    assert abs((p <= 256).mean() - 0.5) < 0.01
    o = traffic._lengths(CHAT["output_len"], n)
    assert o.min() >= 16 and o.max() <= 256
    assert abs(np.median(o) - 96) <= 1
    u = traffic._lengths({"dist": "uniform", "min": 4, "max": 16}, 1300)
    assert Counter(u) == {k: 100 for k in range(4, 17)}


def test_tenant_shares():
    z = traffic.tenant_shares({"zipf_s": 1.0}, 6)
    assert np.allclose(z, [0.408, 0.204, 0.136, 0.102, 0.082, 0.068],
                       atol=1e-3)
    s = traffic.generate(CHAT, 40, 3, 2, 1000)
    assert Counter(r.tenant for r in s.requests) == {0: 84, 1: 36}


def test_token_ids_in_vocab():
    s = traffic.generate(BURST, 16, 9, 6, 50277)
    ids = [x for r in s.requests for x in r.prompt]
    assert min(ids) >= 0 and max(ids) < 50277


def test_benchmark_mixes_generate():
    root = BENCH.parents[1]
    bench = json.loads((root / "BENCHMARK.json").read_text())
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for w in bench["workloads"]:
        cfg = json.loads((root / files[w["config"]]).read_text())
        mix = json.loads((BENCH / "traffic"
                          / f"{w['traffic']}.json").read_text())
        s = traffic.generate(mix, bench["run_seconds"], BIG_SEED,
                             cfg["tenants"], cfg["config"]["vocab_size"])
        assert len(s.requests) > 10
        max_seq = int(cfg["serve_flags"][cfg["serve_flags"].index(
            "--max-seq") + 1])
        assert traffic.longest_request(mix) <= max_seq
