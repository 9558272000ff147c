"""Fig 9 + Fig 10 analog: Azure trace replay — RSS-over-time and
end-to-end latency CDF for OpenWhisk / Photons / Hydra runtime models,
plus the HydraPlatform layer (``hydra-pool``: pre-warmed instance pool,
cross-tenant colocation, snapshot-based function install) and the
HydraCluster layer (``hydra-cluster``: cross-machine placement + spill,
snapshot transfer, adaptive per-node pools).

Two workloads:

  * the synthetic Shahrad-calibrated trace (``gen_trace``) — the
    paper-headline comparisons and the 1-8 node cluster sweep;
  * a real Azure Functions 2019-format trace (``--trace-file``; the
    tiny ``benchmarks/data/azure_sample.csv`` ships in-repo for CI) —
    replayed across ALL registered models at fleet pressure, with
    density (ops/GB-sec) ordering hydra-cluster >= hydra-pool >= hydra
    reported as ``trace.azure.density_ordering``.

``--calibration cal.json`` overrides the paper's startup/memory
constants with values measured on this host by
``bench_startup --emit-calibration`` (see ``repro.core.calibrate``).
``--live`` additionally replays the (thinned) trace through the REAL
gateway stack (``repro.gateway``) and reports live-vs-sim rows —
``trace.live.gateway`` / ``trace.live.sim`` / ``trace.live.vs_sim``
(see docs/benchmarks.md for the methodology); adding
``--calibrate-from-live`` closes the gateway -> calibration -> sim
round trip: the sim re-runs with costs measured from that very replay
and ``trace.live.calibrated_sim`` / ``trace.live.roundtrip`` report
whether it tracks live at least as tightly as the paper-constant sim.

  PYTHONPATH=src python benchmarks/bench_trace.py \\
      --trace-file benchmarks/data/azure_sample.csv \\
      --calibration benchmarks/data/calibration_example.json

Paper headlines to validate: Hydra cuts memory ~83% and p99 tail ~68% vs
OpenWhisk and beats Photons on both; the platform layer then eliminates
the remaining runtime cold starts (strictly fewer cold starts and lower
p99 than plain Hydra on the default trace); the cluster layer beats a
statically partitioned fleet of hydra-pool nodes on cold starts, fleet
p99, and ops/GB-sec at the same aggregate memory.

The cluster rows run under fleet pressure: the trace is the paper's
scaled-down Azure workload, so the per-runtime budget (192 MB) and fleet
memory (3 GB) are scaled to match — keeping instances-per-node and
pool churn at the paper's ratios instead of leaving a 16 GB fleet >90%
idle.
"""
from __future__ import annotations

import argparse
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from repro.core.calibrate import apply_calibration
from repro.core.tracesim import (GB, MB, MODELS, SimParams, Trace, compare,
                                 discover_azure_tables, gen_trace, simulate,
                                 simulate_partitioned)

# scaled-down fleet-pressure regime for the multi-node rows (see module
# docstring); the fleet total stays constant as the node count sweeps
FLEET_PARAMS = dict(runtime_cap=192 * MB, machine_cap=3 * GB)
NODE_SWEEP = (1, 2, 4, 8)

# azure-replay regime: same fleet pressure; the single-node fixed pool is
# sized for the fleet's peak warm capacity (pool_size = n_nodes *
# pool_max) while the cluster's EWMA policy floats between pool_min and
# pool_max per node — the ROADMAP's adaptive-vs-fixed-at-equal-peak
# methodology
AZURE_PARAMS = dict(runtime_cap=192 * MB, machine_cap=3 * GB, n_nodes=4,
                    pool_size=8, pool_min=1, pool_max=2)

DATA_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
AZURE_SAMPLE = os.path.join(DATA_DIR, "azure_sample.csv")


def load_trace_file(path: str, durations: str = None, memory: str = None,
                    target_rps: float = None, max_minutes: int = None,
                    seed: int = 0, stream: bool = False,
                    top_k: int = None, select: str = "top"):
    """Load an Azure-format trace; sibling ``<stem>_durations.csv`` /
    ``<stem>_memory.csv`` tables are auto-discovered when not given.
    ``stream=True`` returns the lazily-expanded ``StreamingTrace``
    (identical invocations, bounded memory — required for ``top_k``
    selection); the default materializes a ``Trace``."""
    found = discover_azure_tables(path)
    durations = durations or found.get("durations_csv")
    memory = memory or found.get("memory_csv")
    if stream or top_k is not None:
        return Trace.stream_azure(path, durations_csv=durations,
                                  memory_csv=memory, target_rps=target_rps,
                                  max_minutes=max_minutes, seed=seed,
                                  top_k=top_k, select=select)
    return Trace.from_azure(path, durations_csv=durations,
                            memory_csv=memory, target_rps=target_rps,
                            max_minutes=max_minutes, seed=seed)


def azure_rows(trace, params: SimParams, models=None) -> list:
    """Replay an Azure-format trace (materialized or streaming) across
    ``models`` (default: all)."""
    res = compare(trace, params, models=models)
    d = trace.describe()
    rows = [{
        "name": "trace.azure.workload",
        "us_per_call": 0.0,
        "derived": (f"invocations={d['invocations']};"
                    f"fns={d['functions']};tenants={d['tenants']};"
                    f"rps={d['mean_rps']:.2f};"
                    f"thinning_keep={d.get('thinning_keep', 1.0):.3f}"),
    }]
    for model, s in res.items():
        rows.append({
            "name": f"trace.azure.{model}",
            "us_per_call": s["p99_s"] * 1e6,
            "derived": (f"requests={s['requests']};"
                        f"ops_per_gb_s={s['ops_per_gb_s']:.3f};"
                        f"mean_mem_mb={s['mean_mem_mb']:.0f};"
                        f"cold_rt={s['cold_runtime']};"
                        f"pool_claims={s['pool_claims']};"
                        f"transfers={s['transfers']};"
                        f"dropped={s['dropped']}"),
        })
    if all(m in res for m in ("hydra", "hydra-pool", "hydra-cluster")):
        hy, hp, hc = (res[m]["ops_per_gb_s"]
                      for m in ("hydra", "hydra-pool", "hydra-cluster"))
        rows.append({
            "name": "trace.azure.density_ordering",
            "us_per_call": 0.0,
            "derived": (f"cluster={hc:.3f}>=pool={hp:.3f}>=hydra={hy:.3f};"
                        f"holds={hc >= hp >= hy}"),
        })
    return rows


def synthetic_rows() -> list:
    trace = gen_trace()
    res = compare(trace)
    rows = []
    for model, s in res.items():
        rows.append({
            "name": f"trace.{model}",
            "us_per_call": s["p99_s"] * 1e6,
            "derived": (f"mean_mem_mb={s['mean_mem_mb']:.0f};"
                        f"peak_mem_mb={s['peak_mem_mb']:.0f};"
                        f"overhead_p99_ms={s['overhead_p99_ms']:.1f};"
                        f"runtimes={s['mean_runtimes']:.1f};"
                        f"cold_rt={s['cold_runtime']};"
                        f"pool_claims={s['pool_claims']};"
                        f"dropped={s['dropped']}"),
        })
    ow, ph = res["openwhisk"], res["photons"]
    hy, hp = res["hydra"], res["hydra-pool"]
    rows.append({
        "name": "trace.hydra_vs_openwhisk",
        "us_per_call": 0.0,
        "derived": (f"mem_reduction={100*(1-hy['mean_mem_mb']/ow['mean_mem_mb']):.0f}%;"
                    f"ovh_p99_reduction="
                    f"{100*(1-hy['overhead_p99_ms']/ow['overhead_p99_ms']):.0f}%"),
    })
    rows.append({
        "name": "trace.hydra_vs_photons",
        "us_per_call": 0.0,
        "derived": (f"mem_reduction={100*(1-hy['mean_mem_mb']/ph['mean_mem_mb']):.0f}%;"
                    f"ovh_p99_reduction="
                    f"{100*(1-hy['overhead_p99_ms']/ph['overhead_p99_ms']):.0f}%"),
    })
    rows.append({
        "name": "trace.pool_vs_hydra",
        "us_per_call": 0.0,
        "derived": (f"cold_rt={hp['cold_runtime']}_vs_{hy['cold_runtime']};"
                    f"p99_delta_ms={1e3*(hy['p99_s']-hp['p99_s']):.1f};"
                    f"mem_reduction="
                    f"{100*(1-hp['mean_mem_mb']/hy['mean_mem_mb']):.0f}%"),
    })

    # ---- cluster: 1 -> 8 node sweep at constant fleet memory ----
    sweep = {}
    for n in NODE_SWEEP:
        p = SimParams(n_nodes=n, **FLEET_PARAMS)
        s = simulate(trace, "hydra-cluster", p).summary()
        sweep[n] = s
        rows.append({
            "name": f"trace.cluster_{n}node",
            "us_per_call": s["p99_s"] * 1e6,
            "derived": (f"cold_rt={s['cold_runtime']};"
                        f"ops_per_gb_s={s['ops_per_gb_s']:.2f};"
                        f"mean_mem_mb={s['mean_mem_mb']:.0f};"
                        f"mean_pool_mb={s['mean_pool_mem_mb']:.0f};"
                        f"transfers={s['transfers']};"
                        f"dropped={s['dropped']}"),
        })

    # ---- cluster vs 4 statically partitioned hydra-pool nodes ----
    p4 = SimParams(n_nodes=4, **FLEET_PARAMS)
    cl = sweep[4]
    st = simulate_partitioned(trace, 4, p4).summary()
    fx = simulate(trace, "hydra-cluster",
                  SimParams(n_nodes=4, adaptive_pool=False,
                            **FLEET_PARAMS)).summary()
    rows.append({
        "name": "trace.cluster_vs_static4",
        "us_per_call": 0.0,
        "derived": (f"cold_rt={cl['cold_runtime']}_vs_{st['cold_runtime']};"
                    f"p99_delta_ms={1e3*(st['p99_s']-cl['p99_s']):.1f};"
                    f"ops_gain="
                    f"{cl['ops_per_gb_s']/st['ops_per_gb_s']:.2f}x"),
    })
    rows.append({
        "name": "trace.adaptive_vs_fixed_pool",
        "us_per_call": 0.0,
        "derived": (f"mean_pool_mb={cl['mean_pool_mem_mb']:.0f}"
                    f"_vs_{fx['mean_pool_mem_mb']:.0f};"
                    f"peak_pool_mb={cl['peak_pool_mem_mb']:.0f}"
                    f"_vs_{fx['peak_pool_mem_mb']:.0f};"
                    f"cold_rt={cl['cold_runtime']}_vs_{fx['cold_runtime']}"),
    })
    return rows


def live_rows(trace_file: str = AZURE_SAMPLE, compress: float = 120.0,
              target_rps: float = 2.0, max_minutes: int = 10,
              pool_size: int = 4, seed: int = 0,
              calibrate_from_live: bool = False,
              calibration_out: str = None) -> list:
    """Live-vs-sim section: replay one thinned trace through the REAL
    gateway stack (``repro.gateway``) and the simulator, and report both
    plus their deltas — the wall-clock counterpart of every simulated
    row above. The cold-start and p99 deltas are the metrics
    ``gateway/validate.py`` enforces in CI.

    ``calibrate_from_live`` closes the round trip: the live replay's
    CalibrationProbe payload becomes a ``hydra-calibration/v1`` overlay,
    the sim re-runs with it, and a ``trace.live.calibrated_sim`` /
    ``trace.live.roundtrip`` row pair reports whether the calibrated sim
    tracks live at least as tightly as the uncalibrated one
    (``calibration_out`` optionally persists the derived JSON for later
    ``--calibration`` runs)."""
    from repro.gateway import load_trace, run_validation

    trace = load_trace(trace_file, target_rps=target_rps,
                       max_minutes=max_minutes, seed=seed)
    report = run_validation(trace, compress=compress, pool_size=pool_size,
                            round_trip=calibrate_from_live)
    live, sim = report["live"], report["sim"]
    tol = report["tolerance"]
    rows = []
    for name, s in (("trace.live.gateway", live), ("trace.live.sim", sim)):
        rows.append({
            "name": name,
            "us_per_call": s["p99_s"] * 1e6,
            "derived": (f"requests={s['requests']};"
                        f"cold_rt={s['cold_runtime']};"
                        f"pool_claims={s['pool_claims']};"
                        f"mean_mem_mb={s['mean_mem_mb']:.0f};"
                        f"dropped={s['dropped']}"),
        })
    rows.append({
        "name": "trace.live.vs_sim",
        "us_per_call": 0.0,
        "derived": (f"cold_rt={tol['cold_live']}_vs_{tol['cold_sim']};"
                    f"cold_tolerance={tol['limit']:.1f};"
                    f"cold_within_tolerance={tol['passed']};"
                    f"p99_delta_s={live['p99_s'] - sim['p99_s']:.3f};"
                    f"compress={compress:g}"),
    })
    if calibrate_from_live and "round_trip" not in report:
        # derivation failed (probe measured nothing): say so loudly and
        # emit a non-finite roundtrip row so validate_rows turns the
        # missing requested artifact into a non-zero exit, not a silent
        # green run
        msg = "; ".join(report.get("failures", [])) \
            or "calibration unavailable"
        print(f"# bench_trace: round trip unavailable: {msg}",
              file=sys.stderr)
        rows.append({
            "name": "trace.live.roundtrip",
            "us_per_call": float("nan"),
            "derived": "calibrated_at_least_as_close=False",
        })
    elif calibrate_from_live:
        cal = report["calibrated_sim"]
        rt = report["round_trip"]
        rows.append({
            "name": "trace.live.calibrated_sim",
            "us_per_call": cal["p99_s"] * 1e6,
            "derived": (f"requests={cal['requests']};"
                        f"cold_rt={cal['cold_runtime']};"
                        f"pool_claims={cal['pool_claims']};"
                        f"mean_mem_mb={cal['mean_mem_mb']:.0f};"
                        f"dropped={cal['dropped']}"),
        })
        rows.append({
            "name": "trace.live.roundtrip",
            "us_per_call": 0.0,
            "derived": (
                f"cold_cal_delta={rt['cold_runtime']['cal_delta']};"
                f"cold_uncal_delta={rt['cold_runtime']['uncal_delta']};"
                f"p99_cal_delta_s={rt['p99_s']['cal_delta']:.3f};"
                f"p99_uncal_delta_s={rt['p99_s']['uncal_delta']:.3f};"
                f"calibrated_at_least_as_close={rt['passed']}"),
        })
        if calibration_out and "calibration" in report:
            from repro.core.calibrate import write_calibration_doc
            write_calibration_doc(calibration_out, report["calibration"])
    return rows


def azure_section(trace_file: str, calibration: str = None,
                  durations: str = None, memory: str = None,
                  target_rps: float = None, max_minutes: int = None,
                  seed: int = 0, models=None, stream: bool = False,
                  top_k: int = None, select: str = "top") -> list:
    """One azure-replay section: fleet-pressure params (optionally
    calibrated), trace load, rows — shared by run() and the CLI."""
    params = SimParams(**AZURE_PARAMS)
    if calibration:
        params = apply_calibration(params, calibration)
    trace = load_trace_file(trace_file, durations=durations, memory=memory,
                            target_rps=target_rps, max_minutes=max_minutes,
                            seed=seed, stream=stream, top_k=top_k,
                            select=select)
    return azure_rows(trace, params, models=models)


def run(trace_file: str = AZURE_SAMPLE, calibration: str = None) -> list:
    """Driver entry point (benchmarks/run.py): synthetic sections plus —
    when the bundled sample (or ``trace_file``) exists — the azure-replay
    section."""
    rows = synthetic_rows()
    if trace_file and os.path.exists(trace_file):
        rows += azure_section(trace_file, calibration)
    return rows


def validate_rows(rows: list) -> list:
    """Sanity gate for CI (sim-smoke): NaN metrics or a replay that
    served zero invocations are failures, not output."""
    errors = []
    if not rows:
        return ["no benchmark rows produced"]
    for row in rows:
        if not math.isfinite(row["us_per_call"]):
            errors.append(f"{row['name']}: non-finite us_per_call")
        for pair in row["derived"].split(";"):
            key, _, val = pair.partition("=")
            if any(tok in ("nan", "-nan", "inf", "-inf")
                   for tok in val.lower().split("_")):
                errors.append(f"{row['name']}: non-finite {key}={val}")
            if key in ("requests", "invocations") and val == "0":
                errors.append(f"{row['name']}: zero invocations replayed")
    return errors


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--trace-file", default=AZURE_SAMPLE,
                    help="Azure Functions 2019-format invocations CSV "
                         "(default: the bundled sample)")
    ap.add_argument("--durations", default=None,
                    help="durations percentile CSV (default: "
                         "<trace>_durations.csv when present)")
    ap.add_argument("--memory", default=None,
                    help="app memory percentile CSV (default: "
                         "<trace>_memory.csv when present)")
    ap.add_argument("--calibration", default=None,
                    help="hydra-calibration/v1 JSON from bench_startup "
                         "--emit-calibration")
    ap.add_argument("--target-rps", type=float, default=None,
                    help="deterministically thin the trace to this mean "
                         "rps (seeded binomial per function-minute)")
    ap.add_argument("--max-minutes", type=int, default=None,
                    help="replay only the first N minutes of the trace")
    ap.add_argument("--seed", type=int, default=0,
                    help="thinning/expansion seed")
    ap.add_argument("--stream", action="store_true",
                    help="replay through the chunked streaming loader "
                         "(bounded memory; byte-identical invocations)")
    ap.add_argument("--top-k", type=int, default=None,
                    help="keep only K function rows of the trace "
                         "(implies --stream; see --select)")
    ap.add_argument("--select", default="top", choices=("top", "stratified"),
                    help="top-K policy: the K busiest rows, or one "
                         "seeded pick per popularity stratum")
    ap.add_argument("--emit-bench", default=None, metavar="PATH",
                    help="also write the schema-versioned "
                         "BENCH_trace.json artifact here (validated "
                         "against the hydra-bench/v1 schema first; see "
                         "benchmarks/bench_artifact.py)")
    ap.add_argument("--models", default=None,
                    help=f"comma-separated subset of {list(MODELS)}")
    ap.add_argument("--synthetic", action="store_true",
                    help="also run the synthetic-trace sections")
    ap.add_argument("--live", action="store_true",
                    help="also replay the (thinned) trace through the "
                         "REAL gateway stack and report live-vs-sim "
                         "deltas (see repro.gateway)")
    ap.add_argument("--live-compress", type=float, default=None,
                    help="wall-clock compression for the --live replay "
                         "(default 120)")
    ap.add_argument("--calibrate-from-live", action="store_true",
                    help="with --live: derive a calibration from the "
                         "live replay itself, re-simulate with it, and "
                         "report trace.live.calibrated_sim / "
                         "trace.live.roundtrip rows (the gateway -> "
                         "calibration -> sim loop)")
    ap.add_argument("--calibration-out", default=None, metavar="PATH",
                    help="with --calibrate-from-live: also write the "
                         "derived hydra-calibration/v1 JSON here")
    args = ap.parse_args(argv)

    if args.calibrate_from_live and not args.live:
        print("bench_trace: --calibrate-from-live requires --live",
              file=sys.stderr)
        return 2
    if args.live_compress is not None and not args.live:
        print("bench_trace: --live-compress requires --live",
              file=sys.stderr)
        return 2
    if args.calibration_out and not args.calibrate_from_live:
        print("bench_trace: --calibration-out requires "
              "--calibrate-from-live", file=sys.stderr)
        return 2

    if args.select != "top" and args.top_k is None:
        print("bench_trace: --select requires --top-k", file=sys.stderr)
        return 2
    if not os.path.isfile(args.trace_file):
        print(f"bench_trace: trace file not found: {args.trace_file}",
              file=sys.stderr)
        return 2
    if not os.access(args.trace_file, os.R_OK):
        print(f"bench_trace: trace file not readable: {args.trace_file}",
              file=sys.stderr)
        return 2

    try:
        rows = azure_section(
            args.trace_file, calibration=args.calibration,
            durations=args.durations, memory=args.memory,
            target_rps=args.target_rps, max_minutes=args.max_minutes,
            seed=args.seed,
            models=args.models.split(",") if args.models else None,
            stream=args.stream, top_k=args.top_k, select=args.select)
    except ValueError as e:
        # unusable trace/window (empty expansion, malformed schema,
        # no minutes in range): a clean diagnostic, not a traceback
        print(f"bench_trace: {e}", file=sys.stderr)
        return 2
    if args.synthetic:
        rows += synthetic_rows()
    if args.live:
        rows += live_rows(args.trace_file,
                          compress=args.live_compress or 120.0,
                          target_rps=args.target_rps or 2.0,
                          max_minutes=args.max_minutes or 10,
                          seed=args.seed,
                          calibrate_from_live=args.calibrate_from_live,
                          calibration_out=args.calibration_out)

    print("name,us_per_call,derived")
    for row in rows:
        print(f"{row['name']},{row['us_per_call']:.1f},{row['derived']}")
    errors = validate_rows(rows)

    if args.emit_bench:
        from benchmarks.bench_artifact import (build_artifact,
                                               validate_artifact,
                                               write_artifact)
        try:
            doc = build_artifact(args.trace_file,
                                 calibration=args.calibration,
                                 target_rps=args.target_rps,
                                 max_minutes=args.max_minutes,
                                 seed=args.seed, top_k=args.top_k,
                                 select=args.select)
        except ValueError as e:
            print(f"bench_trace: --emit-bench: {e}", file=sys.stderr)
            return 2
        bench_errors = validate_artifact(doc)
        if bench_errors:
            # an artifact that fails its own schema is never written
            errors += [f"emit-bench: {e}" for e in bench_errors]
        else:
            write_artifact(doc, args.emit_bench)

    for e in errors:
        print(f"# FAIL {e}", file=sys.stderr)
    return 1 if errors else 0


if __name__ == "__main__":
    from repro.core.executable_cache import configure_compile_cache
    configure_compile_cache()
    raise SystemExit(main())
