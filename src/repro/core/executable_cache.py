"""ExecutableCache: AOT compilation + code-cache sharing (paper §3.3/§3.4).

Programs are compiled ONCE per *program signature* — (architecture family,
entrypoint, abstract shapes, mesh, dtype) — with weights passed as traced
arguments, never closed over. Every tenant whose function shares a signature
therefore shares a single compiled executable: the analog of Graalvisor
co-locating Truffle contexts of one function so JIT code caches are reused.

Compilation happens at registration (AOT, paper §3.4 Native Image analog),
never on the request path. Optionally executables are persisted to disk via
``jax.experimental.serialize_executable`` so a restarted runtime skips
recompilation entirely (the Native-Image-binary-on-disk analog).
"""
from __future__ import annotations

import hashlib
import os
import pickle
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Optional

import jax
from jax.experimental.compilation_cache import compilation_cache as cc


# fixed, in the checkout: the cache directory is part of what lets a later
# process hit, so it is never derived from a temp name, pid or time
DEFAULT_COMPILE_CACHE_DIR = str(
    Path(__file__).resolve().parents[3] / ".jax_cache")


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX has
    already read it; no other directory is set), else
    ``DEFAULT_COMPILE_CACHE_DIR``. Entry points call this once, before
    their first compile; importing this module configures nothing.

    The thresholds are lowered to cache everything — serverless programs
    are small and compile fast, exactly the entries the defaults skip.
    """
    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir:
        cache_dir = DEFAULT_COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()   # a compile before this call would pin "no cache"
    return cache_dir


@dataclass
class CacheEntry:
    key: tuple
    compiled: Any
    compile_s: float
    hits: int = 0
    created_at: float = field(default_factory=time.monotonic)


class ExecutableCache:
    def __init__(self, persist_dir: Optional[str] = None,
                 shared: bool = True):
        """``shared=False`` emulates the per-context-JIT baseline (every
        registration compiles its own copy) for the Fig 4 experiment.
        JAX's own persistent compilation cache sits under this one; it is
        process-global and set up by ``configure_compile_cache``."""
        self._entries: dict[tuple, CacheEntry] = {}
        self._lock = threading.Lock()
        self.persist_dir = persist_dir
        self.shared = shared
        self.total_compile_s = 0.0
        self.compiles = 0        # actual XLA compilations (not disk loads)
        self.disk_hits = 0       # executables deserialized from persist_dir
        if persist_dir:
            os.makedirs(persist_dir, exist_ok=True)

    # ------------------------------------------------------------------
    def _disk_path(self, key: tuple) -> Optional[str]:
        if not self.persist_dir:
            return None
        # stable across processes (builtin hash() is salted per process,
        # which would make every restart miss its own persisted files)
        h = hashlib.sha256(repr(key).encode()).hexdigest()[:16]
        return os.path.join(self.persist_dir, f"exe_{h}.bin")

    def get_or_compile(self, key: tuple,
                       lower_fn: Callable[[], Any],
                       *, fid: Optional[str] = None) -> CacheEntry:
        """lower_fn() must return a jax ``Lowered`` (we .compile() it)."""
        if not self.shared and fid is not None:
            key = key + ("fid", fid)
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                entry.hits += 1
                return entry

        compiled = None
        t0 = time.perf_counter()
        path = self._disk_path(key)
        if path and os.path.exists(path):
            try:
                from jax.experimental import serialize_executable as se
                with open(path, "rb") as f:
                    payload, in_tree, out_tree = pickle.load(f)
                compiled = se.deserialize_and_load(payload, in_tree, out_tree)
            except Exception:
                compiled = None  # stale/incompatible snapshot: recompile
        loaded_from_disk = compiled is not None
        if compiled is None:
            lowered = lower_fn()
            compiled = lowered.compile()
            if path:
                try:
                    from jax.experimental import serialize_executable as se
                    payload, in_tree, out_tree = se.serialize(compiled)
                    tmp = path + ".tmp"
                    with open(tmp, "wb") as f:
                        pickle.dump((payload, in_tree, out_tree), f)
                    os.replace(tmp, path)
                except Exception:
                    pass
        compile_s = time.perf_counter() - t0

        entry = CacheEntry(key=key, compiled=compiled, compile_s=compile_s)
        with self._lock:
            # racing registration of the same signature: first one wins
            existing = self._entries.get(key)
            if existing is not None:
                existing.hits += 1
                return existing
            self._entries[key] = entry
            self.total_compile_s += compile_s
            if loaded_from_disk:
                self.disk_hits += 1
            else:
                self.compiles += 1
        return entry

    # ------------------------------------------------------------------
    def contains(self, key: tuple) -> bool:
        with self._lock:
            return key in self._entries

    def invalidate(self, key: tuple) -> None:
        with self._lock:
            self._entries.pop(key, None)

    def stats(self) -> dict:
        with self._lock:
            return {
                "entries": len(self._entries),
                "hits": sum(e.hits for e in self._entries.values()),
                "compiles": self.compiles,
                "disk_hits": self.disk_hits,
                "total_compile_s": self.total_compile_s,
                "xla_cache_dir": jax.config.jax_compilation_cache_dir
                or None,
            }
