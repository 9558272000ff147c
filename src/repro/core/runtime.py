"""HydraRuntime: one virtualized runtime hosting many functions (paper §3).

The request path mirrors the paper's Listing 1:
  invoke -> registry lookup -> arena (isolate) acquire from pool ->
  AOT-compiled program execution -> arena release.

Registration (paper §3.1/§3.4) materializes weights and AOT-compiles every
entrypoint through the shared ExecutableCache — compilation NEVER happens on
the request path, converting runtime cold starts into arena cold starts.
"""
from __future__ import annotations

import functools
import queue
import threading
import time
from concurrent.futures import Future
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.arena import ArenaPool, tree_bytes
from repro.core.budget import MemoryBudget
from repro.core.executable_cache import ExecutableCache
from repro.core.metrics import Metrics
from repro.core.registry import (CallableSpec, Function, FunctionRegistry,
                                 LMSpec)
from repro.core.tracing import NULL_TRACE, trace_now
from repro.models.programs import ModelProgram

GB = 1 << 30


def registration_budget(spec, prog=None) -> tuple:
    """(registration reservation bytes, one-arena bytes) for a spec — the
    single source of truth for admission math, shared by the runtime's
    reservation and the platform's placement estimate. Pass ``prog`` when
    an LMSpec's ModelProgram is already built."""
    if isinstance(spec, CallableSpec):
        reserve = (tree_bytes(spec.example_args) + tree_bytes(spec.params)
                   + spec.arena_bytes)
        return reserve, spec.arena_bytes
    if isinstance(spec, LMSpec):
        prog = prog or ModelProgram(spec.cfg, remat=False)
        cache = prog.cache_bytes(spec.slots, spec.max_seq)
        return tree_bytes(spec.params) + cache, cache
    raise TypeError(type(spec))


def lm_decode_sample(prog, params, cache, tokens):
    """Decode + greedy-sample step over all slots (the runtime donates
    ``cache``)."""
    logits, new_cache = prog.decode_step(params, cache, {"tokens": tokens})
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), new_cache


def lm_prefill_insert(prog, params, arena_cache, tokens, slot):
    """Prefill ``tokens`` (1, prompt_len), then write the cache into row
    ``slot`` of the arena cache slab (donated by the runtime)."""
    prompt_len = tokens.shape[1]
    logits, cache = prog.prefill(params, {"tokens": tokens})
    out = dict(arena_cache)
    for k in cache:
        if k == "length":
            out[k] = arena_cache[k].at[slot].set(prompt_len)
        else:
            dst, src = out[k], cache[k]
            pad = [(0, a - b) for a, b in zip(dst.shape, src.shape)]
            start = [jnp.int32(0)] * dst.ndim
            start[1] = slot  # batch/slot axis is dim 1 (L, B, ...)
            src = jnp.pad(src, pad).astype(dst.dtype)
            # src padded to full slab shape; restrict to one slot row
            src = jax.lax.slice_in_dim(src, 0, 1, axis=1)
            out[k] = jax.lax.dynamic_update_slice(dst, src, tuple(start))
    first_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return first_tok, out


class HydraRuntime:
    def __init__(self, *,
                 memory_budget_bytes: int = 2 * GB,  # paper: 2 GB per runtime
                 arena_ttl_s: float = 10.0,
                 n_workers: int = 4,
                 executable_cache: Optional[ExecutableCache] = None,
                 janitor: bool = True,
                 hist_max_samples: Optional[int] = None):
        self.metrics = Metrics(hist_max_samples=hist_max_samples)
        self.budget = MemoryBudget(memory_budget_bytes, name="hydra")
        self.registry = FunctionRegistry()
        self.exe_cache = executable_cache or ExecutableCache()
        self.arena_pool = ArenaPool(budget=self.budget, ttl_s=arena_ttl_s,
                                    metrics=self.metrics,
                                    exe_cache=self.exe_cache)
        self._queue: "queue.Queue" = queue.Queue()
        self._workers = [threading.Thread(target=self._worker_loop,
                                          daemon=True, name=f"hydra-w{i}")
                         for i in range(n_workers)]
        self._shutdown = threading.Event()
        for w in self._workers:
            w.start()
        self._janitor = None
        if janitor:
            self._janitor = threading.Thread(target=self._janitor_loop,
                                             daemon=True, name="hydra-janitor")
            self._janitor.start()

    # ------------------------------------------------------------------
    # Registration (paper §3.1)
    # ------------------------------------------------------------------
    def register_function(self, fid: str, spec, *, tenant: str = "default",
                          # hydralint: disable=HL002 — registration on first
                          # invocation is the modeled fn_register_s cost:
                          # jit/compile + snapshot I/O hit the shared
                          # ExecutableCache, not the steady-state path
                          mem_budget: Optional[int] = None) -> bool:
        with self.metrics.timeit("register_s"):
            if isinstance(spec, CallableSpec):
                func = self._register_callable(fid, spec, tenant, mem_budget)
            elif isinstance(spec, LMSpec):
                func = self._register_lm(fid, spec, tenant, mem_budget)
            else:
                raise TypeError(type(spec))
        ok = self.registry.add(func)
        if not ok:
            self.budget.release(func.mem_budget)
        self.metrics.inc("registered", int(ok))
        return ok

    def _register_callable(self, fid, spec: CallableSpec, tenant,
                           mem_budget) -> Function:
        budget = mem_budget or registration_budget(spec)[0]
        self.budget.reserve(budget)
        args_spec = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            spec.example_args)
        params_spec = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), spec.params)
        shapes_key = tuple(
            (tuple(x.shape), str(x.dtype))
            for x in jax.tree.leaves((params_spec, args_spec)))
        key = ("callable", spec.name, shapes_key)
        # fresh closure: defeat jax's in-process pjit cache so executable
        # sharing is provided (and measured) by OUR ExecutableCache only
        raw = spec.fn
        fresh = lambda p, a: raw(p, a)
        entry = self.exe_cache.get_or_compile(
            key, lambda: jax.jit(fresh).lower(params_spec, args_spec),
            fid=fid)
        nb = max(spec.arena_bytes, 8)
        # the factory mints a slab at most once per pooled arena (cold
        # path only); host-zeros + device_put keeps the mint itself free
        # of per-size XLA fill kernels. Warm claims never run this: the
        # slab allocator hands back pooled device memory, scrubbed by the
        # per-signature donate-in-place zeroer registered below
        factory = lambda: {"scratch": jax.device_put(
            np.zeros((nb // 4,), np.float32))}
        arena_sig = ("scratch", nb)
        self.arena_pool.register_signature(
            arena_sig, factory,
            {"scratch": jax.ShapeDtypeStruct((nb // 4,), jnp.float32)})
        return Function(fid=fid, tenant=tenant, spec=spec, mem_budget=budget,
                        entry={"invoke": entry.compiled},
                        arena_sig=arena_sig, arena_factory=factory)

    def _register_lm(self, fid, spec: LMSpec, tenant, mem_budget) -> Function:
        prog = ModelProgram(spec.cfg, remat=False)
        B, S = spec.slots, spec.max_seq
        cache_specs = prog.cache_specs(B, S)
        budget = mem_budget or registration_budget(spec, prog)[0]
        self.budget.reserve(budget)
        params_spec = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), spec.params)
        fkey = spec.family_key

        tok_spec = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        entry_dec = self.exe_cache.get_or_compile(
            fkey + ("decode",),
            lambda: jax.jit(functools.partial(lm_decode_sample, prog),
                            donate_argnums=(1,)).lower(
                params_spec, cache_specs, tok_spec),
            fid=fid)

        def factory():
            return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                cache_specs)

        self.arena_pool.register_signature(("lm",) + fkey, factory,
                                           cache_specs)
        func = Function(fid=fid, tenant=tenant, spec=spec, mem_budget=budget,
                        entry={"decode": entry_dec.compiled},
                        arena_sig=("lm",) + fkey, arena_factory=factory)
        func.prog = prog
        func.params_spec = params_spec
        return func

    def prewarm_arenas(self, fid: str, n: int = 1) -> None:
        """Pre-touch ``n`` slabs for ``fid``'s arena signature off the
        clock, so the function's first invocations are allocation-free
        (paper: pre-allocated cached isolates)."""
        func = self.registry.get(fid)
        self.arena_pool.prealloc(func.arena_sig, func.arena_factory, n,
                                 owner=fid)

    def _lm_prefill_exe(self, func: Function, prompt_len: int):
        """Exact-length prefill program, AOT-compiled + cached on first use
        of this prompt length (production would use length buckets)."""
        spec: LMSpec = func.spec
        prog: ModelProgram = func.prog
        key = spec.family_key + ("prefill", prompt_len)
        cache_specs = prog.cache_specs(spec.slots, spec.max_seq)
        tok_spec = jax.ShapeDtypeStruct((1, prompt_len), jnp.int32)
        slot_spec = jax.ShapeDtypeStruct((), jnp.int32)
        entry = self.exe_cache.get_or_compile(
            key, lambda: jax.jit(functools.partial(lm_prefill_insert, prog),
                                 donate_argnums=(1,)).lower(
                func.params_spec, cache_specs, tok_spec, slot_spec),
            fid=func.fid)
        return entry.compiled

    # ------------------------------------------------------------------
    # Invocation (paper Listing 1)
    # ------------------------------------------------------------------
    def invoke(self, fid: str, args: Any, ctx=None) -> Any:
        return self.invoke_async(fid, args, ctx).result()

    def invoke_async(self, fid: str, args: Any, ctx=None) -> Future:
        # the trace context rides the queue item: the worker thread that
        # dequeues it continues the same request's spans (contextvars
        # would not survive this thread hop)
        fut: Future = Future()
        self._queue.put(("invoke", fid, args, time.perf_counter(), fut, ctx))
        return fut

    def generate(self, fid: str, prompt_tokens, max_new_tokens: int = 16):
        fut: Future = Future()
        self._queue.put(("generate", fid, (prompt_tokens, max_new_tokens),
                         time.perf_counter(), fut, None))
        return fut.result()

    def deregister_function(self, fid: str) -> bool:
        try:
            func = self.registry.get(fid)
        except Exception:
            return False
        ok = self.registry.remove(fid)
        if ok:
            self.budget.release(func.mem_budget)
            self.metrics.inc("deregistered")
        return ok

    # ------------------------------------------------------------------
    def _worker_loop(self):
        while not self._shutdown.is_set():
            try:
                item = self._queue.get(timeout=0.1)
            except queue.Empty:
                continue
            kind, fid, args, t_enq, fut, ctx = item
            if ctx is not None and ctx.sampled:
                # t_enq is already trace_now()'s clock (perf_counter)
                ctx.add_span("dispatch", t_enq, trace_now())
            try:
                if kind == "invoke":
                    result = self._do_invoke(fid, args, ctx)
                else:
                    result = self._do_generate(fid, *args)
                self.metrics.observe("invoke_latency_s",
                                     time.perf_counter() - t_enq)
                fut.set_result(result)
            except Exception as e:  # surface to caller
                fut.set_exception(e)

    def _do_invoke(self, fid: str, args, ctx=None):
        ctx = ctx or NULL_TRACE
        func = self.registry.get(fid)
        func.invocations += 1
        arena = self.arena_pool.acquire(func.arena_sig, func.arena_factory,
                                        owner=fid, ctx=ctx)
        try:
            with ctx.span("compute"):
                result = func.entry["invoke"](func.spec.params, args)
                result = jax.block_until_ready(result)
        finally:
            self.arena_pool.release(arena)
        return result

    def _do_generate(self, fid: str, prompt_tokens, max_new: int):
        func = self.registry.get(fid)
        func.invocations += 1
        spec: LMSpec = func.spec
        prompt = jnp.asarray(prompt_tokens, jnp.int32).reshape(1, -1)
        prefill_exe = self._lm_prefill_exe(func, prompt.shape[1])
        arena = self.arena_pool.acquire(func.arena_sig, func.arena_factory,
                                        owner=fid)
        try:
            tok, cache = prefill_exe(spec.params, arena.buffers, prompt,
                                     jnp.int32(0))
            toks = [int(tok[0])]
            tok = jnp.tile(tok.reshape(1, 1), (spec.slots, 1))
            for _ in range(max_new - 1):
                tok, cache = func.entry["decode"](spec.params, cache, tok)
                toks.append(int(tok[0]))
                tok = tok.reshape(spec.slots, 1)
            arena.buffers = cache   # donated in place; hand back the slab
        finally:
            self.arena_pool.release(arena)
        return toks

    def _janitor_loop(self):
        while not self._shutdown.is_set():
            time.sleep(min(1.0, self.arena_pool.ttl_s / 4))
            self.arena_pool.evict_idle()

    # ------------------------------------------------------------------
    def stats(self) -> dict:
        return {
            "functions": len(self.registry),
            "budget_used": self.budget.used,
            "budget_peak": self.budget.peak,
            "arena": self.arena_pool.stats(),
            "exe_cache": self.exe_cache.stats(),
            "metrics": self.metrics.snapshot(),
        }

    def shutdown(self):
        self._shutdown.set()
        for w in self._workers:
            w.join(timeout=2.0)
        if self._janitor:
            self._janitor.join(timeout=2.0)
        self.arena_pool.drain()
