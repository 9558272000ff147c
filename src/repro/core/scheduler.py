"""Request scheduling: continuous batching for decode (the high-density
serving analog of the paper's many-isolates-per-runtime) and per-tenant
token buckets (the cgroup CPU-share analog, §3.7).

``ContinuousBatcher.step`` is on the profiler's clock as host phases
(``core/tracing.py``): ``hydra.lm.step`` holds one ``hydra.lm.admit`` per
admitted request, one ``hydra.lm.decode`` (the decode dispatch) and one
``hydra.lm.sync`` (the token readback and retirement). No lock is held
across any of them: ``submit`` only ever waits for a list append.

Every program call that yields tokens costs exactly one device-to-host
transfer of its whole token output (``ContinuousBatcher.readbacks``
counts them); tokens fed back to the device stay on the device.

A free slot holds cache length 0: decode attention then reads none of its
KV blocks, and decode leaves its length at 0. ``step`` zeroes the length
of each slot it retires in one device update, with no host read.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tracing import phase


class TokenBucket:
    """Per-tenant rate limiting (cgroup CPU-share analog)."""

    def __init__(self, rate: float, burst: float):
        self.rate = rate
        self.burst = burst
        self._tokens = burst
        self._t = time.monotonic()
        self._lock = threading.Lock()

    def try_take(self, n: float = 1.0) -> bool:
        with self._lock:
            now = time.monotonic()
            self._tokens = min(self.burst,
                               self._tokens + (now - self._t) * self.rate)
            self._t = now
            if self._tokens >= n:
                self._tokens -= n
                return True
            return False


@dataclass
class _Request:
    prompt: list
    max_new: int
    future: Future
    submitted: float           # perf_counter at submit
    slot: int = -1
    emitted: list = field(default_factory=list)


def _free_slots(cache, freed):
    """``cache`` with the slots where ``freed`` is true at length 0."""
    return {**cache, "length": jnp.where(freed, 0, cache["length"])}


class ContinuousBatcher:
    """Slot-based continuous batching over one LM function's decode program.

    All active requests share ONE compiled decode executable and ONE arena
    (the batched cache slab); new requests prefill into free slots while
    others keep decoding — runtime virtualization at the request level.
    """

    def __init__(self, runtime, fid: str):
        self.rt = runtime
        self.fid = fid
        self.func = runtime.registry.get(fid)
        self.spec = self.func.spec
        self.slots = self.spec.slots
        self.pending: list[_Request] = []
        self.active: dict[int, _Request] = {}
        self.free = list(range(self.slots))
        self._lock = threading.Lock()
        self.arena = runtime.arena_pool.acquire(
            self.func.arena_sig, self.func.arena_factory)
        # every slot starts free, whoever held the arena before
        self.cache = _free_slots(self.arena.buffers,
                                 np.ones(self.slots, bool))
        self.cur_tok = jnp.zeros((self.slots, 1), jnp.int32)
        self.steps = 0
        self.readbacks = 0     # device-to-host transfers: steps + admissions

    def submit(self, prompt_tokens, max_new: int) -> Future:
        fut: Future = Future()
        req = _Request(list(prompt_tokens), max_new, fut, time.perf_counter())
        with self._lock:
            self.pending.append(req)
        return fut

    # ------------------------------------------------------------------
    def _admit(self):
        while self.free and self.pending:
            with self._lock:
                req = self.pending.pop(0)
            slot = self.free.pop(0)
            req.slot = slot
            waited_us = int((time.perf_counter() - req.submitted) * 1e6)
            with phase("lm.admit", slot=slot, prompt_len=len(req.prompt),
                       waited_us=waited_us) as sp:
                prompt = jnp.asarray(req.prompt, jnp.int32).reshape(1, -1)
                entry = self.rt._lm_prefill_entry(self.func, prompt.shape[1])
                sp.set_metadata(compiled=int(entry.hits == 0))
                tok, self.cache = entry.compiled(
                    self.spec.params, self.cache, prompt, jnp.int32(slot))
                # tok is (1,): it lands in cur_tok's row on the device
                self.cur_tok = self.cur_tok.at[slot].set(tok)
                req.emitted.append(jax.device_get(tok).item())
                self.readbacks += 1
            self.active[slot] = req

    def step(self) -> int:
        """One scheduler tick: admit, then decode every active slot."""
        with phase("lm.step"):
            self._admit()
            if not self.active:
                return 0
            with phase("lm.decode", active=len(self.active),
                       kv_positions=self._kv_positions()):
                tok, self.cache = self.func.entry["decode"](
                    self.spec.params, self.cache, self.cur_tok)
                self.cur_tok = tok.reshape(self.slots, 1)
                tok.copy_to_host_async()    # starts the copy; no wait
            self.steps += 1
            done = []
            with phase("lm.sync", tokens=len(self.active)):
                host_tok = jax.device_get(tok).tolist()
                self.readbacks += 1
                for slot, req in self.active.items():
                    req.emitted.append(host_tok[slot])
                    if len(req.emitted) >= req.max_new:
                        done.append(slot)
                for slot in done:
                    req = self.active.pop(slot)
                    self.free.append(slot)
                    req.future.set_result(req.emitted)
                if done:
                    self.cache = _free_slots(
                        self.cache, np.isin(np.arange(self.slots), done))
            return len(self.active) + len(done)

    def _kv_positions(self) -> int:
        """Cache positions the next decode attends, summed over the active
        slots: each slot's prompt and every token it has emitted (the last
        is written into the cache by this decode), at most ``max_seq``."""
        cap = self.spec.max_seq
        return sum(min(len(r.prompt) + len(r.emitted), cap)
                   for r in self.active.values())

    def run_until_done(self, max_steps: int = 10_000):
        while (self.active or self.pending) and max_steps > 0:
            self.step()
            max_steps -= 1

    def close(self):
        self.arena.buffers = self.cache
        self.rt.arena_pool.release(self.arena)
