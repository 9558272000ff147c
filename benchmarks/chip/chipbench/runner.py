"""One run of one cell: set-up, one window, the correctness check, and the
result line."""
from __future__ import annotations

import bisect
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass

from chipbench import check, device, kernels, serving, spec, stats, traffic
from chipbench import trace as tr

TRACE_SECONDS = 5.0
PREFILL_KERNELS = {"flash_attention", "ssd_scan"}


@dataclass
class RunView:
    """What a per-layer metric's reader may read."""
    cell: spec.Cell
    window: serving.Window
    trace: tr.Trace | None
    peak: dict            # the device's row of the peaks table
    dims: dict            # the configuration's sizes, from its reference

    def in_trace(self, ev) -> bool:
        w0, w1 = self.trace.window
        return w0 <= ev.start and ev.end <= w1

    def step_modules(self, kind: str) -> list:
        return [m for m in self.trace.step_modules(kind)
                if self.in_trace(m) and m.step >= 0]


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def attribute(trace: tr.Trace, steps: list) -> None:
    """Name each step module prefill or decode, and tie it to the harness
    step whose span holds it; a prefill also gets its prompt length."""
    spans = [(s.start, s.end, int(s.name[len(serving.STEP_SPAN):]))
             for s in trace.spans if s.name.startswith(serving.STEP_SPAN)]
    starts = [s[0] for s in spans]
    seen: dict = {}
    for m in trace.step_modules():
        names = {kernels.kernel_of(o.name) for o in m.ops
                 if "custom-call" in o.name}
        m.kind = "prefill" if names & PREFILL_KERNELS else "decode"
        mid = 0.5 * (m.start + m.end)
        i = bisect.bisect_right(starts, mid) - 1
        if i < 0 or not spans[i][0] <= mid <= spans[i][1]:
            continue
        m.step = spans[i][2]
        if m.kind == "prefill":
            j = seen.get(m.step, 0)
            seen[m.step] = j + 1
            admitted = steps[m.step].admitted
            m.prompt = admitted[j] if j < len(admitted) else None


def breakdown(trace: tr.Trace) -> dict:
    """Device time by operation (a loop that holds other operations is
    left out, its body counts), and the longest idle gaps."""
    ops: dict = {}
    for m in trace.modules:
        if not (trace.window[0] <= m.start and m.end <= trace.window[1]):
            continue
        label = m.kind or m.name.split("(")[0]
        for o, nxt in zip(m.ops, m.ops[1:] + [None]):
            if nxt is not None and o.start <= nxt.start and nxt.end <= o.end:
                continue
            key = f"{label}: {tr.short_name(o.name)}"
            ops[key] = ops.get(key, 0.0) + o.dur
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v * 1e-9] for k, v in top],
            "idle_gaps": tr.idle_gaps(trace)}


def _finite(x):
    return x if x is not None and math.isfinite(x) else None


def run(name: str, seed: int, seconds: float, trace: bool, t_start: float,
        root=spec.ROOT, need_chip: bool = True) -> dict:
    """The result line of one run. ``need_chip=False`` skips the device
    check and the compile cache: the tests drive the rest on the CPU."""
    cell = spec.load_cell(name, root)
    check.gap_limit(cell.config)
    peak = spec.peaks()
    dev = device.prepare(cell.chips, peak, root, need_chip)
    peak = peak.get(dev["kind"])

    cfg = cell.config
    schedule = traffic.generate(cell.traffic, seconds, seed, cfg["tenants"],
                                cfg["config"]["vocab_size"])
    log(f"cell {name}, seed {seed}, {seconds:g}s window: "
        f"{traffic.describe(schedule, seconds)}")
    dep = serving.Deployment(cell, seed, log)
    max_seq = dep.args.max_seq
    if traffic.longest_request(cell.traffic) > max_seq:
        raise ValueError(f"the mix's longest request exceeds max_seq "
                         f"{max_seq}")
    dep.warm(schedule.prompt_lengths, log)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        win = serving.serve(dep, schedule, seconds, trace_dir,
                            min(TRACE_SECONDS, seconds / 2))
        setup_s = win.t0 - t_start
        dev["memory_peak_bytes"] = device.memory_peak_bytes(cell.chips)
        dep.close()
        log(f"window: {len(win.records)} requests, {len(win.steps)} steps, "
            f"{win.compiles} compiles in the window, errors {win.errors[:3]}")
        records = win.records
        result: dict = {}
        if trace:
            t = time.perf_counter()
            trc = tr.load(trace_dir, serving.WINDOW_SPAN)
            attribute(trc, win.steps)
            dev["busy_s"] = trc.busy_ns() * 1e-9
            dev["window_s"] = trc.window_ns * 1e-9
            view = RunView(cell, win, trc, peak,
                           cell.reference.dims(cfg))
            metrics = {}
            for m in cell.per_layer:
                got = cell.reader(m["name"]).read(view)
                if isinstance(got, dict):
                    val, extra = got.pop("value"), got
                else:
                    val, extra = got, {}
                if _finite(val) is not None:
                    metrics[m["name"]] = {"value": val, "unit": m["unit"],
                                          **extra}
            result["breakdown"] = breakdown(trc)
            log(f"trace read in {time.perf_counter() - t:.3f}s: "
                f"{len(trc.modules)} modules, {len(trc.ops)} ops")
        else:
            e2e = stats.end_to_end(records, win.t0, seconds)
            e2e["setup_s"] = setup_s
            metrics = {m["name"]: {"value": _finite(e2e[m["name"]]),
                                   "unit": m["unit"]}
                       for m in cell.end_to_end}
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    t = time.perf_counter()
    verdicts, cmp = check.judge(cell, seed, records)
    correct, checks = verdicts["f32"]
    log(f"reference: {cmp and cmp['requests']} requests, "
        f"{cmp and cmp['positions']} served tokens over "
        f"{cmp and cmp['tenants']} tenant(s) in "
        f"{time.perf_counter() - t:.3f}s")
    for k, v in checks.items():
        rel = "at least" if k == "checked_tenants" else "at most"
        log(f"check {k}: {v['value']} ({rel} {v['limit']})")
    out = {"correct": correct, "attempted": len(records),
           "failed": checks["unfinished"]["value"], "metrics": metrics,
           "device": dev}
    out.update(result)
    out["checks"] = checks
    return out
