"""The profiler trace of a few seconds of the window, reduced to events.

``capture`` starts and stops ``jax.profiler`` with the Python tracer off
(host spans come from ``TraceAnnotation`` only). ``load`` reads the
``.xplane.pb`` it wrote with nothing but JAX. Host and device events share
one clock, in nanoseconds from the start of the profile.

What the reduction finds:

- module events: one per execution of a compiled program on the device
  (the "XLA Modules" line of each ``/device:TPU:n`` plane);
- op events: every HLO operation executed (the "XLA Ops" line), each with
  its HLO instruction text, from which a kernel's shapes can be read;
- host spans the harness wrote (names starting with ``chipbench.``);
- step modules: module events that ran more than ``STEP_MIN_OPS``
  operations, i.e. a model's prefill or decode, not an eager host op.
"""
from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field

SPAN_PREFIX = "chipbench."
STEP_MIN_OPS = 32


@dataclass
class Event:
    name: str
    start: float      # ns from the start of the profile
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Module(Event):
    ops: list = field(default_factory=list)    # Event, in time order
    kind: str = ""                              # "prefill", "decode", ""
    step: int = -1                              # index in the step log
    prompt: int | None = None                   # a prefill's prompt length


@dataclass
class Trace:
    devices: int
    window: tuple          # (start, end) ns of the traced window
    modules: list          # Module, all devices, in time order
    ops: list              # Event, all devices, in time order
    spans: list            # Event, host spans of the harness
    device_ops: list = field(default_factory=list)   # one list per device

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def busy_ns(self) -> float:
        """Time in the window in which an operation ran, averaged over
        the devices."""
        w0, w1 = self.window
        per = [union_ns(Event(o.name, max(o.start, w0), min(o.end, w1))
                        for o in ops if o.end > w0 and o.start < w1)
               for ops in self.device_ops]
        return sum(per) / len(per) if per else 0.0

    def step_modules(self, kind: str | None = None) -> list:
        return [m for m in self.modules if len(m.ops) >= STEP_MIN_OPS
                and (kind is None or m.kind == kind)]


def capture_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def union_ns(events) -> float:
    """Length of the union of the events' intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for e in sorted(events, key=lambda x: x.start):
        if cur_e is None or e.start > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = e.start, e.end
        else:
            cur_e = max(cur_e, e.end)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def nest(parents, children) -> None:
    """Append each child to the ``ops`` of the parent whose interval holds
    its midpoint. Both lists are in time order; parents do not overlap."""
    starts = [p.start for p in parents]
    for c in children:
        mid = 0.5 * (c.start + c.end)
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and parents[i].start <= mid <= parents[i].end:
            parents[i].ops.append(c)


def holder(spans, t: float):
    """The innermost span open at time t, or None."""
    best = None
    for s in spans:
        if s.start <= t <= s.end and (best is None or s.dur < best.dur):
            best = s
    return best


def from_profile(pd, window: tuple | None = None) -> Trace:
    modules, ops, spans, device_ops, devices = [], [], [], [], 0
    lo, hi = float("inf"), 0.0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices += 1
            lines = {ln.name: ln for ln in plane.lines}
            mods = [Module(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in (lines["XLA Modules"].events
                              if "XLA Modules" in lines else [])]
            dev_ops = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in (lines["XLA Ops"].events
                                 if "XLA Ops" in lines else [])]
            mods.sort(key=lambda m: m.start)
            dev_ops.sort(key=lambda o: o.start)
            nest(mods, dev_ops)
            device_ops.append(dev_ops)
            modules.extend(mods)
            ops.extend(dev_ops)
            for e in mods:
                lo, hi = min(lo, e.start), max(hi, e.end)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Event(e.name, e.start_ns,
                                           e.start_ns + e.duration_ns))
    spans.sort(key=lambda s: s.start)
    modules.sort(key=lambda m: m.start)
    ops.sort(key=lambda o: o.start)
    if window is None:
        window = (lo, hi) if modules else (0.0, 0.0)
    return Trace(devices, window, modules, ops, spans, device_ops)


def load(trace_dir: str, window_span: str | None = None) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``. The traced
    window is the host span named ``window_span`` when given."""
    import jax
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    t = from_profile(jax.profiler.ProfileData.from_file(files[-1]))
    if window_span:
        for s in t.spans:
            if s.name == window_span:
                t.window = (s.start, s.end)
    return t


def idle_gaps(trace: Trace, top: int = 10) -> list:
    """The longest gaps between device operations inside the window, each
    named by the harness span open across its middle."""
    gaps = []
    w0, w1 = trace.window
    ops = [o for o in trace.ops if o.end > w0 and o.start < w1]
    prev_end = w0
    for o in ops:
        if o.start > prev_end:
            gaps.append((prev_end, o.start))
        prev_end = max(prev_end, o.end)
    if w1 > prev_end:
        gaps.append((prev_end, w1))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:top]:
        s = holder(trace.spans, 0.5 * (a + b))
        out.append([s.name if s else "no harness span", (b - a) * 1e-9])
    return out


def short_name(hlo: str) -> str:
    """'%fusion.3 = bf16[8,128]{...} fusion(...), kind=...' ->
    'fusion.3 = bf16[8,128] fusion'."""
    head = hlo.split("(", 1)[0]
    parts = head.split("=", 1)
    name = parts[0].strip().lstrip("%")
    if len(parts) == 1:
        return name
    rhs = parts[1].strip().split(" ")
    shape = rhs[0].split("{", 1)[0]
    op = rhs[-1] if len(rhs) > 1 else ""
    return f"{name} = {shape} {op}".strip()
