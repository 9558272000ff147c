"""Device idle time charged to the program's host phases.

The program opens profiler spans named ``hydra.<phase>`` around its host
work (``core/tracing.py``): the LM batcher's ``lm.step`` holds
``lm.admit``, ``lm.decode`` and ``lm.sync``, and the janitor, pool
refills and Python's collector run as ``runtime.evict_idle``,
``platform.boot`` and ``gc`` beside the serving loop. Each idle
nanosecond of the traced window (no operation runs on any device) is
charged by the first rule that applies:

1. a background phase open on any thread: that phase (the one opened
   last, where several are open);
2. the innermost ``hydra.`` span open on the serving thread, the thread
   whose line holds the ``lm.step`` spans;
3. ``outside``.

The charges partition the idle time, so they sum to it exactly.

The spans are the trace's ``program_spans`` where its reduction keeps
them. Else they are read from the profile the run's trace was reduced
from, while the run still holds it: the newest ``.xplane.pb`` under the
temporary directory the runner profiles into, taken only when its
``chipbench.window`` span is the trace's window. A program without such
spans, or a profile that cannot be read, gives no spans, and the readers
read nothing.
"""
from __future__ import annotations

import glob
import os
import tempfile
from dataclasses import dataclass

from chipbench import trace as tr
from chipbench.serving import WINDOW_SPAN

PREFIX = "hydra."
HOST_PLANE = "/host:CPU"
BACKGROUND = ("gc", "runtime.evict_idle", "platform.boot")
STEP = "lm.step"
OUTSIDE = "outside"
TRACE_DIRS = "chipbench-trace-*"      # runner.run's mkdtemp prefix


@dataclass
class Span(tr.Event):
    thread: int = 0        # the index of its line in the host plane


def program_spans(pd) -> list:
    """The host events named ``hydra.*`` of a profile, each with its
    thread line, in time order."""
    out = []
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append(Span(e.name, e.start_ns,
                                    e.start_ns + e.duration_ns, i))
    out.sort(key=lambda s: s.start)
    return out


def _window_of(pd):
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW_SPAN:
                    return e.start_ns, e.start_ns + e.duration_ns
    return None


def profile_on_disk(trace: tr.Trace):
    """The profile ``trace`` was reduced from, or None."""
    import jax
    files = glob.glob(os.path.join(tempfile.gettempdir(), TRACE_DIRS, "**",
                                   "*.xplane.pb"), recursive=True)
    if not files:
        return None
    try:
        pd = jax.profiler.ProfileData.from_file(
            max(files, key=os.path.getmtime))
    except RuntimeError:   # a profile it cannot read gives no spans
        return None
    w = _window_of(pd)
    if w is None or abs(w[0] - trace.window[0]) > 1 \
            or abs(w[1] - trace.window[1]) > 1:
        return None
    return pd


def spans_of(trace: tr.Trace) -> list:
    """The trace's ``program_spans``, read from the profile on disk (and
    kept on the trace) where its reduction left them out."""
    if getattr(trace, "program_spans", None) is None:
        pd = profile_on_disk(trace)
        trace.program_spans = program_spans(pd) if pd is not None else []
    return trace.program_spans


def idle_intervals(trace: tr.Trace) -> list:
    """(start, end) of the times in the window when no operation runs on
    any device, in time order."""
    w0, w1 = trace.window
    out, prev = [], w0
    for o in trace.ops:
        if o.end <= w0 or o.start >= w1:
            continue
        if o.start > prev:
            out.append((prev, o.start))
        prev = max(prev, o.end)
    if w1 > prev:
        out.append((prev, w1))
    return out


def serving_thread(spans: list):
    """The thread line holding the most ``lm.step`` spans, or None."""
    counts: dict = {}
    for s in spans:
        if s.name == PREFIX + STEP:
            counts[s.thread] = counts.get(s.thread, 0) + 1
    return max(counts, key=counts.get) if counts else None


def owners(spans: list, window: tuple) -> list:
    """The window cut into (start, end, phase) pieces by the rules above;
    the pieces are contiguous and cover the window."""
    w0, w1 = window
    serving = serving_thread(spans)
    background = {PREFIX + b for b in BACKGROUND}
    edges = []
    for s in spans:
        bg = s.name in background
        if not bg and s.thread != serving:
            continue
        a, b = max(s.start, w0), min(s.end, w1)
        if a < b:
            edges.append((a, 1, s, bg))
            edges.append((b, 0, s, bg))
    edges.sort(key=lambda e: (e[0], e[1]))
    open_bg, open_loop = [], []
    out, t = [], w0

    def owner():
        # the latest opened wins; among spans opened together, the shorter
        held = open_bg or open_loop
        if not held:
            return OUTSIDE
        s = max(held, key=lambda x: (x.start, -x.end))
        return s.name[len(PREFIX):]

    for at, opening, s, bg in edges:
        if at > t:
            out.append((t, at, owner()))
            t = at
        held = open_bg if bg else open_loop
        if opening:
            held.append(s)
        else:
            held.remove(s)
    if w1 > t:
        out.append((t, w1, owner()))
    return out


def charge(trace: tr.Trace, spans: list) -> dict:
    """Idle ns of the window by phase (rules above)."""
    idle = idle_intervals(trace)
    got: dict = {}
    i = 0
    for a, b, name in owners(spans, trace.window):
        while i < len(idle) and idle[i][1] <= a:
            i += 1
        j = i
        while j < len(idle) and idle[j][0] < b:
            lo, hi = max(a, idle[j][0]), min(b, idle[j][1])
            if hi > lo:
                got[name] = got.get(name, 0.0) + (hi - lo)
            j += 1
    return got


def charged(run):
    """The run's idle ns by phase (rules above), or None where the
    program left no spans in the window."""
    trace = run.trace
    spans = spans_of(trace)
    w0, w1 = trace.window
    if not trace.devices or not any(s.start < w1 and s.end > w0
                                    for s in spans):
        return None
    return charge(trace, spans)


def started_in_window(run, name: str) -> list:
    """The spans named ``hydra.<name>`` that began in the window."""
    w0, w1 = run.trace.window
    return [s for s in spans_of(run.trace) if s.name == PREFIX + name
            and w0 <= s.start < w1]
