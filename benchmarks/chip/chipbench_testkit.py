"""Helpers for the chip benchmark's CPU tests: a checkout-like root that
holds a tiny configuration of each reference model, so that the harness
can be driven end to end on the CPU (the device check skipped)."""
from __future__ import annotations

import json
import os
from pathlib import Path

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parents[1]

TINY = {
    "qwen2": {
        "base": "qwen2.5-3b.x2",
        "config": dict(hidden_size=64, intermediate_size=128,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=2, vocab_size=512),
        "program": dict(d_model=64, d_ff=128, n_layers=2, n_heads=4,
                        n_kv_heads=2, vocab_size=512, head_dim=16),
        # widest gaps at this size on the CPU, seeds 1-3, 2**31, 2**31 + 77,
        # 2**31 + 129 and 5e9: sound runs 0.009 to 0.059, the float8
        # control 0.21 to 0.50
        "gap_limit": 0.1,
        "mix": {"arrivals": {"mean_rate_per_s": 8.0},
                "tenants": {"shares": [0.7, 0.3]},
                "prompt_len": {"dist": "lognormal", "median": 16,
                               "sigma": 0.7, "round_up_to": [8, 16, 32, 64]},
                "output_len": {"dist": "lognormal", "median": 8,
                               "sigma": 0.6, "min": 2, "max": 24}},
    },
    "mamba2": {
        "base": "mamba2-780m.x6",
        "config": dict(d_model=64, n_layer=2, vocab_size=500),
        "layer_defaults": dict(d_state=16, headdim=16, chunk_size=8),
        "program": dict(d_model=64, n_layers=2, vocab_size=512, ssm_state=16,
                        ssm_head_dim=16, ssm_chunk=8),
        # widest gaps at this size on the CPU, seeds 1-3, 2**31 and
        # 2**31 + 77: sound runs 0.0004 to 0.0037, the float8 control 0.029
        # to 0.074
        "gap_limit": 0.01,
        "mix": {"arrivals": {"mean_rate_per_s": 8.0,
                             "phases": [{"seconds": 0.5, "rate_scale": 2.5},
                                        {"seconds": 1.5, "rate_scale": 0.5}]},
                "tenants": {"zipf_s": 1.0},
                "prompt_len": {"dist": "lognormal", "median": 16,
                               "sigma": 0.5, "round_up_to": [16, 32]},
                "output_len": {"dist": "lognormal", "median": 8,
                               "sigma": 0.6, "min": 2, "max": 24}},
    },
}
SERVE_FLAGS = ["--pool", "2", "--runtime-budget-gb", "1", "--slots", "4",
               "--max-seq", "96"]


def tiny_config(model: str) -> dict:
    t = TINY[model]
    cfg = json.loads((BENCH / "configs" / f"{t['base']}.json").read_text())
    cfg["name"] = f"tiny-{model}"
    cfg["config"].update(t["config"])
    cfg["program"].update(t["program"])
    if "layer_defaults" in t:
        cfg["layer_defaults"].update(t["layer_defaults"])
    cfg["serve_flags"] = SERVE_FLAGS
    cfg["check"]["gap_limit"] = t["gap_limit"]
    return cfg


def make_root(tmp: Path, model: str) -> tuple:
    """A root with BENCHMARK.json naming one tiny cell of ``model``; the
    library, references and metric readers are the real ones. Returns
    (root, cell name)."""
    bench_dir = tmp / "bench"
    (bench_dir / "configs").mkdir(parents=True)
    (bench_dir / "traffic").mkdir()
    for d in ("chipbench", "reference", "metrics"):
        os.symlink(BENCH / d, bench_dir / d)
    os.symlink(REPO / "src", tmp / "src")
    cfg = tiny_config(model)
    (bench_dir / "configs" / f"{cfg['name']}.json").write_text(
        json.dumps(cfg))
    (bench_dir / "traffic" / "mix.json").write_text(
        json.dumps(TINY[model]["mix"]))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = f"{cfg['name']}.mix"
    bench["paths"] = ["bench"]
    bench["configs"] = [{"name": cfg["name"], "source": "test",
                         "file": f"bench/configs/{cfg['name']}.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": cell, "config": cfg["name"],
                           "traffic": "mix", "chips": 1, "why": "test"}]
    for m in bench["per_layer"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp, cell
