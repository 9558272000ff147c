"""Hydra runtime behaviour: registration, invocation, isolation semantics,
code-cache sharing, arena pooling, budgets, continuous batching."""
import time

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import (CallableSpec, ContinuousBatcher, ExecutableCache,
                        FunctionNotRegisteredError, HydraOOMError,
                        HydraRuntime, LMSpec, MemoryBudget)
from repro.core.arena import ArenaPool
from repro.kernels.ops import set_kernel_mode
from repro.models.programs import ModelProgram

from conftest import bf16_params


def make_rt(**kw):
    kw.setdefault("memory_budget_bytes", 1 << 30)
    kw.setdefault("janitor", False)
    return HydraRuntime(**kw)


def simple_spec(name="affine"):
    def fn(params, args):
        return {"y": args["x"] * params["w"] + 1.0}
    return CallableSpec(name=name, fn=fn,
                        example_args={"x": jnp.ones((64,), jnp.float32)},
                        params={"w": jnp.full((64,), 2.0)})


# ---------------------------------------------------------------------------
def test_register_invoke_deregister():
    rt = make_rt()
    try:
        assert rt.register_function("f1", simple_spec())
        out = rt.invoke("f1", {"x": jnp.full((64,), 3.0)})
        assert float(out["y"][0]) == 7.0
        # duplicate registration rejected
        assert not rt.register_function("f1", simple_spec())
        assert rt.deregister_function("f1")
        with pytest.raises(FunctionNotRegisteredError):
            rt.invoke("f1", {"x": jnp.ones((64,))})
        assert not rt.deregister_function("f1")
    finally:
        rt.shutdown()


def test_executable_cache_shared_across_tenants():
    """Two tenants registering the same program compile ONCE (paper §3.3)."""
    rt = make_rt()
    try:
        rt.register_function("a/f", simple_spec(), tenant="a")
        rt.register_function("b/f", simple_spec(), tenant="b")
        stats = rt.exe_cache.stats()
        # one shared program entry + one shared arena-zeroer entry (the
        # slab scrubber compiles once per signature, at registration)
        assert stats["entries"] == 2
        assert stats["hits"] == 1
    finally:
        rt.shutdown()


def test_executable_cache_unshared_baseline():
    """shared=False = the per-context JIT baseline (compiles per fid)."""
    rt = make_rt(executable_cache=ExecutableCache(shared=False))
    try:
        rt.register_function("a/f", simple_spec(), tenant="a")
        rt.register_function("b/f", simple_spec(), tenant="b")
        # two per-fid program copies + the (always-shared) arena zeroer
        assert rt.exe_cache.stats()["entries"] == 3
    finally:
        rt.shutdown()


def test_slab_isolation_cross_owner_zeroed_same_owner_donated():
    """Slab allocator semantics: a slab handed across owners is scrubbed
    on-device (indistinguishable from a fresh zeroed arena); a slab
    claimed back by its own donor keeps its contents untouched."""
    pool = ArenaPool(ttl_s=1e9)
    sig = ("slab", 4096)
    factory = lambda: {"buf": jnp.zeros((1024,), jnp.float32)}
    pool.register_signature(
        sig, factory, {"buf": jax.ShapeDtypeStruct((1024,), jnp.float32)})

    a = pool.acquire(sig, owner="fn-a")
    a.buffers = {"buf": a.buffers["buf"] + 7.0}     # fn-a dirties the slab
    pool.release(a)

    b = pool.acquire(sig, owner="fn-a")             # donor reclaims it
    assert b is a
    assert float(b.buffers["buf"][0]) == 7.0        # contents preserved
    pool.release(b)

    c = pool.acquire(sig, owner="fn-b")             # cross-owner handover
    assert c is a
    assert float(jnp.max(jnp.abs(c.buffers["buf"]))) == 0.0   # scrubbed
    pool.release(c)

    counters = pool.metrics.counters
    assert counters["arena.cold"] == 1              # one slab ever minted
    assert counters["arena.reuse"] == 1
    assert counters["arena.zeroed"] == 1


def test_prealloc_pretouches_slabs_off_the_clock():
    pool = ArenaPool(ttl_s=1e9)
    calls = []

    def factory():
        calls.append(1)
        return {"buf": jnp.zeros((256,), jnp.float32)}

    pool.prealloc(("sig",), factory, 3, owner="fn")
    assert len(calls) == 3                 # n slabs actually materialized
    assert pool.idle_count == 3
    cold = pool.metrics.counters["arena.cold"]
    reuse = pool.metrics.counters.get("arena.reuse", 0)
    arenas = [pool.acquire(("sig",), owner="fn") for _ in range(3)]
    assert len(calls) == 3                 # claims are pure pool pops...
    assert pool.metrics.counters["arena.cold"] == cold
    # ...and pre-assigned slabs skip even the scrub (donated reuse)
    assert pool.metrics.counters["arena.reuse"] == reuse + 3
    for a in arenas:
        pool.release(a)


def test_arena_pool_warm_and_ttl():
    pool = ArenaPool(ttl_s=0.2)
    factory = lambda: {"buf": jnp.zeros((1024,), jnp.float32)}
    a = pool.acquire(("sig",), factory)
    pool.release(a)
    b = pool.acquire(("sig",), factory)
    assert b is a                                  # warm hit
    pool.release(b)
    assert pool.metrics.counters["arena.warm"] == 1
    time.sleep(0.3)
    released = pool.evict_idle()
    assert released == a.nbytes
    assert pool.idle_count == 0


def test_budget_oom():
    b = MemoryBudget(1000)
    b.reserve(800)
    with pytest.raises(HydraOOMError):
        b.reserve(300)
    b.release(500)
    b.reserve(300)
    assert b.used == 600
    assert b.peak == 800


def test_runtime_budget_admission():
    rt = make_rt(memory_budget_bytes=4 << 20)   # 4 MB runtime
    try:
        with pytest.raises(HydraOOMError):
            rt.register_function(
                "big", simple_spec(), mem_budget=16 << 20)
    finally:
        rt.shutdown()


def test_lm_generate_deterministic_and_warm():
    rt = make_rt(memory_budget_bytes=2 << 30)
    try:
        cfg = get_config("qwen2.5-3b").reduced()
        params = bf16_params(ModelProgram(cfg))
        rt.register_function("lm", LMSpec(cfg=cfg, params=params,
                                          max_seq=64, slots=1))
        t1 = rt.generate("lm", list(range(8)), max_new_tokens=6)
        cold = rt.metrics.counters["arena.cold"]
        t2 = rt.generate("lm", list(range(8)), max_new_tokens=6)
        assert t1 == t2
        assert rt.metrics.counters["arena.cold"] == cold  # pool hit
        assert rt.metrics.counters["arena.warm"] >= 1
    finally:
        rt.shutdown()


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "gemma3-1b"])
def test_lm_registers_and_generates_through_pallas_kernels(arch):
    """Registration AOT-compiles decode through the Pallas kernels (here in
    interpret mode; compiled on a TPU). Each layer's window must reach the
    kernels as a static None or, for gemma3's scanned local:global
    pattern, as a runtime operand. Tokens equal the jnp reference path's."""
    cfg = get_config(arch).reduced()
    params = bf16_params(ModelProgram(cfg))
    prompt = list(range(3, 15))   # longer than gemma3's reduced window (8)
    toks = {}
    for mode in ("interpret", "ref"):
        set_kernel_mode(mode)
        rt = make_rt(memory_budget_bytes=2 << 30)
        try:
            rt.register_function("lm", LMSpec(cfg=cfg, params=params,
                                              max_seq=64, slots=2))
            toks[mode] = rt.generate("lm", prompt, max_new_tokens=6)
        finally:
            rt.shutdown()
            set_kernel_mode("auto")
    assert len(toks["interpret"]) == 6
    assert toks["interpret"] == toks["ref"]


def test_continuous_batcher_matches_single_path():
    rt = make_rt(memory_budget_bytes=2 << 30)
    try:
        cfg = get_config("granite-moe-1b-a400m").reduced()
        params = bf16_params(ModelProgram(cfg))
        rt.register_function("lm", LMSpec(cfg=cfg, params=params,
                                          max_seq=64, slots=3))
        single = rt.generate("lm", list(range(8)), max_new_tokens=5)
        b = ContinuousBatcher(rt, "lm")
        futs = [b.submit(list(range(8)), 5) for _ in range(5)]
        b.run_until_done()
        outs = [f.result() for f in futs]
        assert all(o == single for o in outs)
        # 5 requests over 3 slots share decode steps
        assert b.steps < 5 * 5
        b.close()
    finally:
        rt.shutdown()


def test_invoke_latency_metrics_populated():
    rt = make_rt()
    try:
        rt.register_function("f", simple_spec())
        for _ in range(5):
            rt.invoke("f", {"x": jnp.ones((64,))})
        snap = rt.metrics.snapshot()
        assert snap["hists"]["invoke_latency_s"]["count"] == 5
    finally:
        rt.shutdown()
