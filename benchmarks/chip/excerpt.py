#!/usr/bin/env python3
"""Record an excerpt of a cell's traced window, for the CPU tests.

  python3 benchmarks/chip/excerpt.py --workload <name> --seed <n> \\
      --seconds <s> --out benchmarks/chip/testdata/<file>.json.gz

One traced run of the cell, as ``run.py --trace 1`` makes it (its result
line is printed), then ``--length`` seconds of its profile, from 20 ms
before the first admission (``hydra.lm.admit``) that fits in the traced
window: the device's module and op lines, and the host events of the
harness (``chipbench.*``) and of the program (``hydra.*``), each host
line kept in its place so that a span keeps its thread. The file holds
``planes`` (events as [name index, start ns, duration ns]), ``names``,
``window`` (the excerpt's start and end) and ``steps`` (the harness's
step log: each step's admitted prompt lengths and the keys each decode
read). Run on the chip.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

LEAD_NS = 20e6
DEVICE_LINES = ("XLA Modules", "XLA Ops")
HOST_PREFIXES = ("chipbench.", "hydra.")


def cut(pd, steps: list, start: float, seconds: float) -> dict:
    """``seconds`` of the profile ``pd`` from ``start`` (ns), with the
    step log of every step up to the last one the excerpt shows."""
    from chipbench import serving
    end = start + seconds * 1e9
    names: dict = {}
    planes = []
    last_step = -1
    for plane in pd.planes:
        device = plane.name.startswith("/device:TPU:")
        if not device and plane.name != "/host:CPU":
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in DEVICE_LINES:
                continue
            events = []
            for e in line.events:
                if e.start_ns + e.duration_ns < start or e.start_ns > end:
                    continue
                if not device and not e.name.startswith(HOST_PREFIXES):
                    continue
                if e.name.startswith(serving.STEP_SPAN):
                    last_step = max(last_step,
                                    int(e.name[len(serving.STEP_SPAN):]))
                events.append([names.setdefault(e.name, len(names)),
                               e.start_ns, e.duration_ns])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes, "names": list(names), "window": [start, end],
            "steps": {str(i): {"admitted": steps[i].admitted,
                               "ctx": steps[i].ctx}
                      for i in range(last_step + 1)}}


def first_admission(spans: list, window: tuple, seconds: float):
    """The excerpt's start: 20 ms before the first admission whose
    excerpt lies in the window; None when none does."""
    for s in spans:
        if s.name != "hydra.lm.admit":
            continue
        start = s.start - LEAD_NS
        if start >= window[0] and start + seconds * 1e9 <= window[1]:
            return start
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True, help="run seed")
    ap.add_argument("--seconds", type=float, default=50,
                    help="window of the run, s")
    ap.add_argument("--length", type=float, default=0.15,
                    help="length of the excerpt, s")
    ap.add_argument("--out", required=True, help="the .json.gz to write")
    args = ap.parse_args(argv)
    from chipbench import phases, runner

    got = {}
    attribute = runner.attribute

    def attribute_and_cut(trace, steps):
        # the run still holds its profile on disk here
        attribute(trace, steps)
        pd = phases.profile_on_disk(trace)
        if pd is None:
            return
        start = first_admission(phases.program_spans(pd), trace.window,
                                args.length)
        if start is not None:
            got["doc"] = cut(pd, steps, start, args.length)

    runner.attribute = attribute_and_cut
    out = runner.run(args.workload, args.seed, args.seconds, True, T_START)
    print(json.dumps(out))
    if "doc" not in got:
        print("[excerpt] no admission fits in the traced window",
              file=sys.stderr)
        return 1
    with open(args.out, "wb") as f:
        f.write(gzip.compress(json.dumps(got["doc"]).encode()))
    print(f"[excerpt] wrote {args.out}: {os.path.getsize(args.out)} bytes",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
