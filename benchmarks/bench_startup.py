"""Fig 1 analog: startup latency + memory footprint per virtualization layer.

Layers measured on this host:
  runtime-cold   build a HydraRuntime + compile a function (new process
                 worker = runtime boot + first JIT)
  exe-cache-warm registration that hits the shared executable cache
  arena-cold     first isolate allocation
  arena-warm     pooled isolate acquisition (paper: < 500 us)
  snap-restore   platform snapshot -> evict -> restore round trip (the
                 zero-recompile warm path)

``--emit-calibration out.json`` additionally writes the measurements as
a ``hydra-calibration/v1`` JSON (see ``repro.core.calibrate``) mapping
them onto the simulator's ``SimParams`` fields, so trace replays
(``bench_trace --calibration out.json``) use THIS host's costs instead
of the paper constants:

  PYTHONPATH=src python benchmarks/bench_startup.py \\
      --emit-calibration calibration.json
"""
from __future__ import annotations

import argparse
import os
import resource
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp

from benchmarks.functions import catalog
from repro.core import HydraPlatform, HydraRuntime
from repro.core.arena import ArenaPool

MB = 1 << 20


def measure() -> tuple:
    """Run the Fig-1 measurements; returns (csv rows, measured dict of
    calibratable SimParams fields)."""
    rows = []
    measured = {}
    specs = catalog()
    spec = specs["jv/filehashing"]

    # runtime cold: fresh runtime + fresh compile. The Fig-1 row reports
    # the combined wall time; the calibration splits it — the boot leg
    # maps onto hydra_runtime_cold_s (charged per simulated cold start)
    # and the first-install leg onto fn_register_s (charged per first
    # function load), so nothing is double-counted and the sim's cost
    # ordering (snapshot restore << full register) survives calibration.
    # The RSS high-water delta across the boot alone is a best-effort
    # stand-in for the runtime's base footprint (only trusted — and only
    # emitted — when the allocator actually grew the process image).
    rss_unit = 1 if sys.platform == "darwin" else 1024  # ru_maxrss: B vs KB
    rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * rss_unit
    t0 = time.perf_counter()
    rt = HydraRuntime(janitor=False)
    boot_s = time.perf_counter() - t0
    rss1 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * rss_unit
    rt.register_function("f", spec)
    cold_s = time.perf_counter() - t0
    rows.append({"name": "startup.runtime_cold", "us_per_call": cold_s * 1e6,
                 "derived": f"boot_us={boot_s*1e6:.0f};"
                            f"budget={rt.budget.used}B"})
    measured["hydra_runtime_cold_s"] = boot_s
    measured["fn_register_s"] = cold_s - boot_s
    if rss1 - rss0 > 8 * MB:
        measured["hydra_runtime_base"] = rss1 - rss0

    # warm registration (executable cache hit, second tenant)
    t0 = time.perf_counter()
    rt.register_function("f2", spec, tenant="t2")
    warm_s = time.perf_counter() - t0
    rows.append({"name": "startup.register_warm", "us_per_call": warm_s * 1e6,
                 "derived": f"speedup={cold_s/warm_s:.1f}x"})

    # arena cold vs warm. The process's first-ever allocation includes a
    # one-time jnp.zeros JIT; holding it while acquiring again forces a
    # second pool-miss WITHOUT that compile — the steady-state cold cost
    # the simulator charges per cold isolate (same boot-vs-install split
    # as the runtime leg above).
    pool = ArenaPool(ttl_s=60)
    factory = lambda: {"kv": jnp.zeros((256, 1024), jnp.float32)}  # 1 MB
    # hydralint: disable=HL009 — warmup is held ON PURPOSE so the next
    # acquire misses the pool (a release would turn the cold-path
    # measurement into a warm hit); the pool is function-local and dies
    # with the benchmark
    warmup = pool.acquire(("kv",), factory)      # one-time JIT happens here
    t0 = time.perf_counter()
    a = pool.acquire(("kv",), factory)           # pool empty: cold alloc
    cold_a = time.perf_counter() - t0
    pool.release(a)
    t0 = time.perf_counter()
    pool.acquire(("kv",), factory)               # pool hit: warm
    warm_a = time.perf_counter() - t0
    pool.release(warmup)
    rows.append({"name": "startup.arena_cold", "us_per_call": cold_a * 1e6,
                 "derived": f"bytes={a.nbytes}"})
    rows.append({"name": "startup.arena_warm", "us_per_call": warm_a * 1e6,
                 "derived": f"speedup={cold_a/max(warm_a,1e-9):.1f}x"})
    measured["isolate_cold_s"] = cold_a
    measured["isolate_warm_s"] = warm_a
    rt.shutdown()

    # platform snapshot -> evict -> restore round trip: the restore leg
    # is the sim's snapshot_restore_s (install a snapshotted fn vs a
    # first full register)
    with tempfile.TemporaryDirectory() as snapdir:
        plat = HydraPlatform(pool_size=1, snapshot_dir=snapdir)
        try:
            plat.register_function("cal/f", specs["jv/filehashing"],
                                   tenant="cal")
            plat.invoke("cal/f", spec.example_args)
            plat.snapshot("cal/f")
            plat.evict("cal/f")
            t0 = time.perf_counter()
            plat.restore("cal/f")
            restore_s = time.perf_counter() - t0
        finally:
            plat.shutdown()
    rows.append({"name": "startup.snapshot_restore",
                 "us_per_call": restore_s * 1e6,
                 "derived": f"vs_cold={cold_s/max(restore_s,1e-9):.1f}x"})
    measured["snapshot_restore_s"] = restore_s
    return rows, measured


def run() -> list:
    rows, _ = measure()
    return rows


def main(argv=None) -> list:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--emit-calibration", metavar="PATH", default=None,
                    help="write measured costs as a hydra-calibration/v1 "
                         "JSON usable by bench_trace --calibration")
    args = ap.parse_args(argv)
    rows, measured = measure()
    for row in rows:
        print(f"{row['name']},{row['us_per_call']:.1f},{row['derived']}")
    if args.emit_calibration:
        import platform as host_platform

        from repro.core.calibrate import write_calibration
        doc = write_calibration(
            args.emit_calibration, measured,
            meta={"source": "bench_startup",
                  "host": host_platform.node() or "unknown"})
        print(f"# wrote {args.emit_calibration}: "
              f"{sorted(doc['measured'])}")
    return rows


if __name__ == "__main__":
    from repro.core.executable_cache import configure_compile_cache
    configure_compile_cache()
    main()
