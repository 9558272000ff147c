#!/usr/bin/env python3
"""Readings that a configuration's correctness limit is set from.

For each seed, in one process: the cell's deployment with that seed's
weights serves a short window at the cell's own load, every request due
in it is followed to its end, the program's state is freed, and the
sample a run compares is run through the float32 reference (the sound
reading) and, with ``--control``, through the float8 control (the
reading the limit must stay below). ``--fault`` plants one of
``chipbench.faults`` in the decode step first. Where the configuration
has a limit, each reading also goes through the gate that decides a
run's ``correct`` (``check.verdict``): ``correct`` for the served tokens,
``correct_fp8`` for the control's first-ranked tokens in their place.
One JSON line per seed.

  python3 benchmarks/chip/limits.py --workload <name> \\
      --seeds 11,12,13 --seconds 10 --control [--fault state_unchanged]

Run on the chip. The limit goes into the configuration file by hand,
with the readings in PERF.md.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default="sound")
    args = ap.parse_args()
    from chipbench import check, device, faults, serving, spec, traffic
    from chipbench.runner import log
    cell = spec.load_cell(args.workload)
    device.prepare(cell.chips, spec.peaks(), spec.ROOT)
    step = faults.planted(args.fault)
    if step is not None:
        from repro.core import runtime
        runtime.lm_decode_sample = step
    cfg = cell.config
    precisions = ("f32", "fp8") if args.control else ("f32",)
    for seed in [int(x) for x in args.seeds.split(",")]:
        t = time.perf_counter()
        sched = traffic.generate(cell.traffic, args.seconds, seed,
                                 cfg["tenants"], cfg["config"]["vocab_size"])
        dep = serving.Deployment(cell, seed, log)
        try:
            dep.warm(sched.prompt_lengths, log)
            win = serving.serve(dep, sched, args.seconds)
        finally:
            dep.close()
        got = {"seed": seed, "fault": args.fault}
        if cfg["check"]["gap_limit"] is None:
            cmp = check.compare(cell, seed, win.records, precisions)
        else:
            verdicts, cmp = check.judge(cell, seed, win.records, precisions)
            for p, (ok, checks) in verdicts.items():
                got["correct" if p == "f32" else f"correct_{p}"] = ok
            got["checks"] = verdicts["f32"][1]
        got.update({
            **{f"widest_{p}": v for p, v in (cmp or {"widest": {}})
               ["widest"].items()},
            "requests": cmp and cmp["requests"],
            "positions": cmp and cmp["positions"],
            "tenants": cmp and cmp["tenants"],
            "unfinished": sum(not r.finished for r in win.records),
            "longest": max(r.prompt_len + r.max_new for r in win.records),
            "seconds": time.perf_counter() - t})
        print(json.dumps(got), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
