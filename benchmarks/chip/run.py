#!/usr/bin/env python3
"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

  python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
      --seconds <s> --trace <0|1>

Runs from the root of a checkout, on the machine that holds the chips the
cell asks for: it stops with a non-zero exit and no result when JAX finds
no TPU, a TPU missing from ``chipbench/peaks.json``, or too few chips. It
sets up the cell's deployment (set-up time ends when the first request is
due), serves the cell's traffic open loop for ``--seconds``, follows every
request due in the window to its end, frees the program's state, and
checks a sample of the served tokens against the configuration's plain
float32 reference. The last line of standard output is one JSON object;
with ``--trace 1`` its metrics are the per-layer ones, read from a
profiler trace of the window's last seconds.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from chipbench import device, runner
    try:
        out = runner.run(args.workload, args.seed, args.seconds,
                         bool(args.trace), T_START)
    except device.NoChip as e:
        print(f"[chipbench] refused: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
