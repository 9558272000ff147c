"""Warm request-path overhead: claim -> args -> dispatch -> release.

Two levels, both in microseconds of pure platform overhead (no emulated
function duration — the program is a trivial affine kernel):

**Arena level** (before/after the slab allocator):

  hotpath.arena.legacy_devput  the pre-slab per-claim cost: mint host
                               zeros + ``device_put`` them on every
                               claim (what ``ArenaPool.acquire`` paid
                               before slabs existed — the "before")
  hotpath.arena.zeroed_reuse   slab handover across owners: pooled pop
                               + jitted donate-in-place zero fill (the
                               cross-tenant "after")
  hotpath.arena.donated_reuse  slab handover back to the same owner:
                               pooled pop only (the same-function
                               "after")

**Request level** (the budgeted numbers): wall latency of a fully warm
``HydraRuntime.invoke`` — registry lookup, slab claim, executable
dispatch, block, release — with host-side request args built per call
exactly as the gateway's ``TraceWorkload.args_for`` does. Reported as
mean/p99 ms over ``--iters`` serial invokes.

**Tracing level**: the same warm invoke through the request-tracing
layer (``repro.core.tracing``) — ``invoke_traced_off`` carries the
no-op ``NULL_TRACE`` an unsampled gateway request pays (delta vs plain
budget-gated at ~0) and ``invoke_traced_on`` the fully-sampled span
path (loose absolute budget; sampling is opt-in).

``--budget PATH`` compares the request-level numbers (and the zeroed
slab handover) against a committed budget JSON and exits non-zero on
any overrun — the CI ``bench-artifact`` job runs exactly that, so a
change that drags allocation, compilation, or host copies back onto
the warm path fails the build. Budgets are deliberately loose (5-10x
a dev-container measurement): they catch order-of-magnitude
regressions — an eager ``device_put`` or a per-request compile — not
machine jitter.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from repro.core.arena import ArenaPool
from repro.core.registry import CallableSpec
from repro.core.runtime import HydraRuntime

DEFAULT_BUDGET = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "data", "overhead_budget.json")
VEC = 64
ARENA_BYTES = 1 << 20            # 1 MB scratch slab, like a small function


def _affine(params, args):
    return {"y": args["x"] * params["w"] + params["b"]}


def _spec() -> CallableSpec:
    import jax.numpy as jnp
    return CallableSpec(name="hotpath", fn=_affine,
                        example_args={"x": jnp.ones((VEC,), jnp.float32)},
                        params={"w": jnp.full((VEC,), 2.0, jnp.float32),
                                "b": jnp.full((VEC,), 1.0, jnp.float32)},
                        arena_bytes=ARENA_BYTES)


def _percentile(sorted_vals: list, q: float) -> float:
    return sorted_vals[min(len(sorted_vals) - 1,
                           int(round(q * (len(sorted_vals) - 1))))]


def _series(fn, iters: int, warmup: int = 20) -> dict:
    for _ in range(warmup):
        fn()
    vals = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        vals.append(time.perf_counter() - t0)
    vals.sort()
    return {"iters": iters,
            "mean": sum(vals) / len(vals),
            "p50": _percentile(vals, 0.50),
            "p99": _percentile(vals, 0.99)}


def bench_arena(iters: int) -> dict:
    """The slab allocator's claim paths vs the pre-slab per-claim
    ``device_put`` allocation, isolated from dispatch."""
    nb = ARENA_BYTES

    # before: every claim minted host zeros and copied them to device
    def legacy_devput():
        jax.block_until_ready(jax.device_put(
            np.zeros((nb // 4,), np.float32)))

    pool = ArenaPool(ttl_s=1e9)
    sig = ("scratch", nb)
    factory = lambda: {"scratch": jax.device_put(
        np.zeros((nb // 4,), np.float32))}
    pool.prealloc(sig, factory, 1, owner="fn-a")

    flip = ["fn-a"]

    def zeroed_reuse():           # ownership changes on every claim
        flip[0] = "fn-b" if flip[0] == "fn-a" else "fn-a"
        pool.release(pool.acquire(sig, owner=flip[0]))

    def donated_reuse():          # same owner claims its slab back
        pool.release(pool.acquire(sig, owner="fn-a"))

    return {"legacy_devput": _series(legacy_devput, iters),
            "zeroed_reuse": _series(zeroed_reuse, iters),
            "donated_reuse": _series(donated_reuse, iters)}


def bench_invoke(iters: int) -> tuple:
    """Fully warm end-to-end invoke (the budgeted request path), plus
    the same invoke through the tracing layer — disabled (the
    ``NULL_TRACE`` every unsampled gateway request carries: one
    sampling decision + no-op spans, budget-gated at ~0 delta) and
    fully sampled (span objects + clock reads + breakdown, the opt-in
    ``--trace-sample`` cost, loose absolute budget)."""
    from repro.core.tracing import Tracer

    rt = HydraRuntime(n_workers=2, janitor=False)
    try:
        rt.register_function("hot/fn", _spec())
        rt.prewarm_arenas("hot/fn", 1)
        compiles0 = rt.exe_cache.stats()["compiles"]
        cold0 = rt.metrics.snapshot()["counters"].get("arena.cold", 0)

        def invoke():
            # host-side payload per request, as the gateway builds it
            rt.invoke("hot/fn", {"x": np.full((VEC,), 3.0, np.float32)})

        tracer_off = Tracer(0.0)

        def invoke_traced_off():
            ctx = tracer_off.start_request("hot/fn")
            rt.invoke("hot/fn", {"x": np.full((VEC,), 3.0, np.float32)},
                      ctx=ctx)
            ctx.finish("ok")

        # bounded export window: a long --iters run must not grow memory
        tracer_on = Tracer(1.0, max_traces=64, hist_max_samples=64)

        def invoke_traced_on():
            ctx = tracer_on.start_request("hot/fn")
            rt.invoke("hot/fn", {"x": np.full((VEC,), 3.0, np.float32)},
                      ctx=ctx)
            ctx.finish("ok")

        series = _series(invoke, iters)
        traced_off = _series(invoke_traced_off, iters)
        traced_on = _series(invoke_traced_on, iters)
        series["compiles_during"] = (rt.exe_cache.stats()["compiles"]
                                     - compiles0)
        series["cold_allocs"] = (rt.metrics.snapshot()["counters"]
                                 .get("arena.cold", 0) - cold0)
        return series, traced_off, traced_on
    finally:
        rt.shutdown()


def measure(iters: int) -> dict:
    plain, traced_off, traced_on = bench_invoke(iters)
    ms = lambda s: {k: (v * 1e3 if isinstance(v, float) else v)
                    for k, v in s.items()}
    off_ms, on_ms = ms(traced_off), ms(traced_on)
    plain_ms = ms(plain)
    return {"arena_us": {name: {k: (v * 1e6 if isinstance(v, float) else v)
                                for k, v in s.items()}
                         for name, s in bench_arena(iters).items()},
            "invoke_ms": plain_ms,
            "invoke_traced_ms": {
                "off": off_ms, "on": on_ms,
                # the gated number: what every UNSAMPLED request pays
                # for tracing being compiled in (expected ~0; negative
                # means jitter, which the budget treats as within)
                "off_delta_mean": off_ms["mean"] - plain_ms["mean"],
            }}


def check_budget(result: dict, budget_doc: dict) -> list:
    """Budget overruns (empty = within budget). Keys of
    ``budget_doc['budgets']`` name the gated numbers."""
    budgets = budget_doc.get("budgets") or {}
    gated = {
        "warm_invoke_ms_mean": result["invoke_ms"]["mean"],
        "warm_invoke_ms_p99": result["invoke_ms"]["p99"],
        "arena_zeroed_reuse_us_mean":
            result["arena_us"]["zeroed_reuse"]["mean"],
        "arena_donated_reuse_us_mean":
            result["arena_us"]["donated_reuse"]["mean"],
        "tracing_off_delta_ms_mean":
            result["invoke_traced_ms"]["off_delta_mean"],
        "traced_invoke_ms_mean":
            result["invoke_traced_ms"]["on"]["mean"],
    }
    errors = []
    for name, limit in budgets.items():
        got = gated.get(name)
        if got is None:
            errors.append(f"unknown budget key: {name}")
        elif not math.isfinite(got) or got > limit:
            errors.append(f"{name}: measured {got:.3f} exceeds "
                          f"budget {limit:.3f}")
    return errors


def run(iters: int = 200) -> list:
    """benchmarks/run.py entry: rows in the common csv shape."""
    res = measure(iters)
    rows = []
    for name, s in res["arena_us"].items():
        rows.append({"name": f"hotpath.arena.{name}",
                     "us_per_call": s["mean"],
                     "derived": f"p99_us={s['p99']:.1f}"})
    inv = res["invoke_ms"]
    rows.append({"name": "hotpath.invoke_warm",
                 "us_per_call": inv["mean"] * 1e3,
                 "derived": f"p99_ms={inv['p99']:.3f};"
                            f"compiles={inv['compiles_during']}"})
    tr = res["invoke_traced_ms"]
    rows.append({"name": "hotpath.invoke_traced_off",
                 "us_per_call": tr["off"]["mean"] * 1e3,
                 "derived": f"delta_ms={tr['off_delta_mean']:.4f}"})
    rows.append({"name": "hotpath.invoke_traced_on",
                 "us_per_call": tr["on"]["mean"] * 1e3,
                 "derived": f"p99_ms={tr['on']['p99']:.3f}"})
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--iters", type=int, default=300,
                    help="timed iterations per series (after 20 warmups)")
    ap.add_argument("--budget", metavar="PATH", nargs="?",
                    const=DEFAULT_BUDGET, default=None,
                    help="overhead budget JSON to gate against (no value: "
                         "the committed benchmarks/data/overhead_budget."
                         "json); exits 1 on any overrun")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also dump the raw measurement document here")
    args = ap.parse_args(argv)

    res = measure(args.iters)
    arena = res["arena_us"]
    legacy = arena["legacy_devput"]["mean"]
    print(f"# warm claim path, {args.iters} iters "
          f"(arena {ARENA_BYTES >> 20} MB)")
    for name in ("legacy_devput", "zeroed_reuse", "donated_reuse"):
        s = arena[name]
        print(f"hotpath.arena.{name},{s['mean']:.1f}us,"
              f"p99={s['p99']:.1f}us,"
              f"vs_legacy={legacy / max(s['mean'], 1e-9):.1f}x")
    inv = res["invoke_ms"]
    print(f"hotpath.invoke_warm,mean={inv['mean']:.3f}ms,"
          f"p99={inv['p99']:.3f}ms,compiles={inv['compiles_during']},"
          f"cold_allocs={inv['cold_allocs']}")
    tr = res["invoke_traced_ms"]
    print(f"hotpath.invoke_traced_off,mean={tr['off']['mean']:.3f}ms,"
          f"p99={tr['off']['p99']:.3f}ms,"
          f"delta_vs_plain={tr['off_delta_mean'] * 1e3:+.1f}us")
    print(f"hotpath.invoke_traced_on,mean={tr['on']['mean']:.3f}ms,"
          f"p99={tr['on']['p99']:.3f}ms")

    if args.json:
        with open(args.json, "w") as f:
            json.dump(res, f, indent=1, sort_keys=True)
            f.write("\n")
    if args.budget:
        with open(args.budget) as f:
            budget_doc = json.load(f)
        errors = check_budget(res, budget_doc)
        for e in errors:
            print(f"# FAIL {e}", file=sys.stderr)
        if errors:
            return 1
        print(f"# within budget ({os.path.basename(args.budget)}): "
              + ", ".join(sorted((budget_doc.get("budgets") or {}))))
    return 0


if __name__ == "__main__":
    from repro.core.executable_cache import configure_compile_cache
    configure_compile_cache()
    raise SystemExit(main())
