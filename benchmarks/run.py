"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Roofline numbers (the dry-run
artifacts) are summarized from experiments/dryrun JSONs when present.
"""
from __future__ import annotations

import glob
import json
import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import (bench_code_cache, bench_coldstart, bench_efficiency,
                        bench_isolate_scaling, bench_latency, bench_serving,
                        bench_startup, bench_trace)

MODULES = [
    ("fig1_startup", bench_startup),
    ("fig3_isolate_scaling", bench_isolate_scaling),
    ("fig4_code_cache", bench_code_cache),
    ("fig5_fig8_coldstart", bench_coldstart),
    ("fig6_efficiency", bench_efficiency),
    ("fig7_latency", bench_latency),
    ("fig9_fig10_trace", bench_trace),
    ("serving_density", bench_serving),
]


def roofline_rows() -> list:
    rows = []
    for path in sorted(glob.glob("experiments/dryrun/*.json")):
        d = json.load(open(path))
        if d.get("tag"):
            continue
        r = d["roofline"]
        rows.append({
            "name": f"roofline.{d['mesh']}.{d['arch']}.{d['shape']}",
            "us_per_call": r["t_bound"] * 1e6,
            "derived": (f"bottleneck={r['bottleneck']};"
                        f"t_c={r['t_compute_s']:.5f};"
                        f"t_m={r['t_memory_s']:.5f};"
                        f"t_n={r['t_collective_s']:.5f};"
                        f"useful={d['useful_flops_frac']:.3f};"
                        f"fit_gb={d['hbm_fit_bytes']/2**30:.2f}"),
        })
    return rows


def main() -> None:
    print("name,us_per_call,derived")
    failures = []
    for tag, mod in MODULES:
        try:
            for row in mod.run():
                print(f"{row['name']},{row['us_per_call']:.1f},"
                      f"{row['derived']}", flush=True)
        except Exception as e:
            failures.append((tag, repr(e)))
            traceback.print_exc(file=sys.stderr)
    for row in roofline_rows():
        print(f"{row['name']},{row['us_per_call']:.1f},{row['derived']}")
    if failures:
        print(f"# {len(failures)} benchmark failures: {failures}",
              file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.core.executable_cache import configure_compile_cache
    configure_compile_cache()
    main()
