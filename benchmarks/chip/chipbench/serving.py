"""The system under test, set up and driven through one window.

Program surface the benchmark depends on: ``repro.launch.serve``'s
``build_parser``/``build_target`` (the deployment from ``serve.py`` flags),
``HydraPlatform.register_function(..., LMSpec, eager=True)`` and
``runtime_for``, ``ContinuousBatcher.submit/step/close``, and the
batcher's ``active`` slots, whose requests carry ``future`` and the tokens
``emitted`` so far. The weights are the benchmark's own
(``chipbench.weights``), checked against the program's parameter shapes.

The window is open loop. A load-generator thread submits each request at
its due time. The serving loop, standing in for the runtime's worker
loop, steps every tenant's batcher that has work, round robin, and after
each step reads every request's tokens: a token is delivered when the
step that produced it returns.
"""
from __future__ import annotations

import gc
import math
import queue
import threading
import time
from contextlib import nullcontext
from dataclasses import dataclass, field

import jax

from chipbench import weights
from chipbench.compiles import CompileCounter
from chipbench.stats import Record

LEAD_S = 0.05          # the loop is running before the first due request
DRAIN_S = 60.0         # requests due in the window may finish this late
IDLE_WAIT_S = 0.002
WINDOW_SPAN = "chipbench.window"
STEP_SPAN = "chipbench.step."


@dataclass
class Step:
    """One ``step()`` of one tenant's batcher."""
    tenant: int
    start: float
    end: float
    admitted: list = field(default_factory=list)   # prompt lengths, in order
    ctx: list = field(default_factory=list)        # keys each decode read


@dataclass
class Window:
    t0: float
    seconds: float
    records: list
    steps: list
    compiles: int = 0
    errors: list = field(default_factory=list)
    trace_start: float = math.nan


class Deployment:
    """The cell's deployment: a platform built from the configuration's
    ``serve.py`` flags, one registered LM function and one batcher per
    tenant, each tenant with its own weights from the seed."""

    def __init__(self, cell, seed: int, log):
        from repro.configs.base import ArchConfig
        from repro.core import LMSpec
        from repro.core.scheduler import ContinuousBatcher
        from repro.launch.serve import build_parser, build_target
        from repro.models.programs import ModelProgram

        cfg = cell.config
        self.arch = ArchConfig(**cfg["program"])
        self.args = build_parser().parse_args(cfg["serve_flags"])
        self.n = cfg["tenants"]
        layout = cell.reference.layout(cfg)
        weights.check_layout(layout, jax.eval_shape(
            ModelProgram(self.arch, remat=False).init,
            jax.random.PRNGKey(0)))
        self.platform = build_target(self.args)
        t = time.perf_counter()
        make = weights.make_tree(layout)
        self.params = []
        for i in range(self.n):
            self.params.append(make(weights.seed_words(seed, i)))
            jax.block_until_ready(self.params[-1])
        log(f"weights: {self.n} tenant(s) in "
            f"{time.perf_counter() - t:.3f}s")
        t = time.perf_counter()
        fids = []
        for i, p in enumerate(self.params):
            fid = f"tenant{i}/{cfg['name']}"
            self.platform.register_function(
                fid, LMSpec(cfg=self.arch, params=p,
                            max_seq=self.args.max_seq,
                            slots=self.args.slots),
                tenant=f"tenant{i}", eager=True)
            fids.append(fid)
        log(f"registered in {time.perf_counter() - t:.3f}s; placement "
            f"{self.platform.placement()}")
        self.batchers = [ContinuousBatcher(self.platform.runtime_for(f), f)
                         for f in fids]

    def warm(self, prompt_lengths: list, log) -> None:
        """Prefill every length the window will send, with every slot of
        every tenant in use at once; decode two tokens each."""
        t = time.perf_counter()
        slots = self.args.slots
        futs = []
        for b in self.batchers:
            for i in range(max(slots, len(prompt_lengths))):
                P = prompt_lengths[i % len(prompt_lengths)]
                futs.append(b.submit([1] * P, 2))
        while any(b.active or b.pending for b in self.batchers):
            for b in self.batchers:
                if b.active or b.pending:
                    b.step()
        for f in futs:
            f.result()
        log(f"warm-up: prompt lengths {prompt_lengths}, {len(futs)} "
            f"requests in {time.perf_counter() - t:.3f}s")

    def close(self) -> None:
        """Stop the platform and free every array it held on the device,
        so that the reference runs on an empty chip."""
        caches = []
        for b in self.batchers:
            caches.append(b.cache)
            b.close()
        self.platform.shutdown()
        for tree in caches + self.params:
            for leaf in jax.tree.leaves(tree):
                if not leaf.is_deleted():
                    leaf.delete()
        self.batchers, self.params, self.platform = [], [], None
        gc.collect()


def _annotate(on: bool, name: str):
    return jax.profiler.TraceAnnotation(name) if on else nullcontext()


def serve(dep: Deployment, schedule, seconds: float, trace_dir=None,
          trace_seconds: float = 0.0) -> Window:
    """Drive one window of ``seconds`` and follow every request due in it
    to its end. With ``trace_dir``, the last ``trace_seconds`` of the
    window are profiled."""
    from chipbench import trace as tr

    batchers = dep.batchers
    n = len(batchers)
    reqs = sorted(schedule.requests, key=lambda r: r.due_s)
    t0 = time.perf_counter() + LEAD_S
    close, deadline = t0 + seconds, t0 + seconds + DRAIN_S
    records = [Record(r.rid, r.tenant, len(r.prompt), r.max_new,
                      due=t0 + r.due_s, prompt=r.prompt) for r in reqs]
    inbox = [queue.SimpleQueue() for _ in range(n)]
    locks = [threading.Lock() for _ in range(n)]
    wake = threading.Event()
    win = Window(t0, seconds, records, [])
    tracing = trace_dir is not None

    def loadgen():
        for req, rec in zip(reqs, records):
            delay = rec.due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            with locks[req.tenant]:
                fut = batchers[req.tenant].submit(req.prompt, req.max_new)
                rec.submitted = time.perf_counter()
                inbox[req.tenant].put((fut, rec))
            wake.set()

    gen = threading.Thread(target=loadgen, name="chipbench-loadgen",
                           daemon=True)
    inflight = [[] for _ in range(n)]
    broken: dict = {}
    span = None
    trace_from = close - trace_seconds

    def drain(t):
        with locks[t]:
            while True:
                try:
                    fut, rec = inbox[t].get_nowait()
                except queue.Empty:
                    return
                if t in broken:
                    rec.failed = True
                else:
                    inflight[t].append((fut, rec))

    counter = CompileCounter()
    counter.__enter__()
    try:
        gen.start()
        counted = False
        while True:
            now = time.perf_counter()
            if tracing and span is None and now >= trace_from:
                win.trace_start = now
                jax.profiler.start_trace(
                    trace_dir, profiler_options=tr.capture_options())
                span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
                span.__enter__()
            if now >= close and not counted:
                win.compiles, counted = counter.compiles, True
                if span is not None:
                    span.__exit__(None, None, None)
                    jax.profiler.stop_trace()
            for t in range(n):
                drain(t)
            if now >= close and not gen.is_alive() and not any(inflight):
                for t in range(n):
                    drain(t)
                if not any(inflight):
                    break
            if now >= deadline:
                for fl in inflight:
                    for _, rec in fl:
                        rec.failed = True
                break
            worked = False
            for t, b in enumerate(batchers):
                if t in broken or not inflight[t]:
                    continue
                start = time.perf_counter()
                try:
                    with _annotate(tracing, f"{STEP_SPAN}{len(win.steps)}"):
                        b.step()
                except Exception as e:   # the tenant's requests fail
                    broken[t] = repr(e)
                    win.errors.append(f"tenant {t}: {e!r}")
                    for _, rec in inflight[t]:
                        rec.failed = True
                    inflight[t] = []
                    continue
                end = time.perf_counter()
                drain(t)
                win.steps.append(_harvest(t, b, inflight, start, end))
                worked = True
            if not worked:
                with _annotate(tracing, "chipbench.idle"):
                    wake.wait(IDLE_WAIT_S)
                    wake.clear()
    finally:
        counter.__exit__(None, None, None)
        gen.join(timeout=DRAIN_S)
    return win


def _harvest(t: int, b, inflight: list, start: float, end: float) -> Step:
    """Credit every token the step produced to its request at ``end``."""
    step = Step(t, start, end)
    active = {id(r.future): r for r in b.active.values()}
    keep = []
    new_admits = []
    for fut, rec in inflight[t]:
        before = len(rec.tokens)
        if fut.done():
            try:
                toks = list(fut.result())
            except Exception:    # a request the program failed
                rec.failed = True
                continue
        elif id(fut) in active:
            toks = list(active[id(fut)].emitted)
            keep.append((fut, rec))
        else:
            keep.append((fut, rec))
            continue
        new = len(toks) - before
        if new <= 0:
            continue
        rec.tokens = toks
        rec.token_times.extend([end] * new)
        if before == 0:
            rec.admitted = start
            new_admits.append(rec)
        if before > 0 or new > 1:
            step.ctx.append(rec.prompt_len + len(toks) - 1)
    inflight[t] = keep
    step.admitted = [r.prompt_len for r in
                     sorted(new_admits, key=lambda r: r.submitted)]
    return step
