#!/usr/bin/env python3
"""Finds a cell's knee: serves its traffic at several rates, one window
each, in one process (set-up paid once), and prints one JSON line per
rate. A rate is sustained while the queue does not grow through the
window: the last quarter's queue wait stays near the first quarter's and
the drain after the window stays short.

  python3 benchmarks/chip/sweep.py --workload <name> --rates 1,1.5,2 \\
      --seconds 30 --seed <n>

Run on the chip; the rates it finds are written into the traffic file by
hand, so the benchmark's own runs never search.
"""
import argparse
import copy
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def quarter_waits(records, t0, seconds):
    """Mean queue wait (ms) of the requests due in the first and the last
    quarter of the window."""
    def mean_wait(lo, hi):
        w = [(r.admitted - r.due) * 1e3 for r in records
             if lo <= r.due - t0 < hi and r.admitted == r.admitted]
        return sum(w) / len(w) if w else None
    q = seconds / 4
    return mean_wait(0, q), mean_wait(3 * q, seconds)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    from chipbench import device, serving, spec, stats, traffic
    from chipbench.runner import log
    cell = spec.load_cell(args.workload)
    device.prepare(cell.chips, spec.peaks(), spec.ROOT)
    cfg = cell.config
    dep = serving.Deployment(cell, args.seed, log)
    warmed = False
    for rate in [float(x) for x in args.rates.split(",")]:
        mix = copy.deepcopy(cell.traffic)
        mix["arrivals"]["mean_rate_per_s"] = rate
        sched = traffic.generate(mix, args.seconds, args.seed,
                                 cfg["tenants"], cfg["config"]["vocab_size"])
        if not warmed:
            dep.warm(sorted(set(cell.traffic["prompt_len"]["round_up_to"])),
                     log)
            warmed = True
        win = serving.serve(dep, sched, args.seconds)
        end = max((r.token_times[-1] for r in win.records if r.token_times),
                  default=win.t0)
        first, last = quarter_waits(win.records, win.t0, args.seconds)
        e2e = stats.end_to_end(win.records, win.t0, args.seconds)
        ttft = [stats.ttft_s(r) * 1e3 for r in win.records]
        print(json.dumps({
            "rate": rate, "requests": len(win.records),
            "unfinished": sum(not r.finished for r in win.records),
            "ttft_p50_ms": stats.percentile(ttft, 50), **e2e,
            "wait_first_q_ms": first, "wait_last_q_ms": last,
            "drain_s": end - win.t0 - args.seconds,
            "compiles": win.compiles,
            "occupancy": (sum(len(s.ctx) for s in win.steps if s.ctx)
                          / max(1, sum(1 for s in win.steps if s.ctx)))}),
            flush=True)
        time.sleep(1.0)
    dep.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
