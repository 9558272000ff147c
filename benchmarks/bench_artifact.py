"""CI-tracked benchmark artifact: the trace-replay trajectory as one
schema-versioned JSON document.

``bench_trace`` prints rows for humans; this module emits (and checks)
``BENCH_trace.json`` — the committed, machine-diffable record of the
reproduction's headline numbers: per-model density (ops/GB-s), p50/p99,
cold starts, and mean/peak memory from the full streaming replay of the
bundled Azure sample, plus trace provenance (file digest, thinning,
selection), the streaming loader's peak buffered invocations, an
optional live gateway smoke leg, and the git SHA that produced it.

The CI ``bench-artifact`` job regenerates the document on every PR and
fails on **schema drift** (the committed and regenerated documents must
have the same key structure — a metric silently disappearing is a
regression of the artifact contract) or a **density-ordering
regression** (the paper's ``hydra-cluster >= hydra-pool >= hydra``
ordering must keep holding). Metric *values* are expected to move as the
models evolve — that moving history, committed PR over PR, is the
trajectory, comparable against the paper's Fig 9/10 shapes.

CLI::

    PYTHONPATH=src python benchmarks/bench_artifact.py \\
        --out BENCH_trace.json --gateway-smoke \\
        --check-against BENCH_trace.json
"""
from __future__ import annotations

import argparse
import hashlib
import json
import math
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.bench_trace import AZURE_PARAMS, AZURE_SAMPLE
from repro.core.calibrate import apply_calibration
from repro.core.tracing import SUMMARY_KEYS
from repro.core.tracesim import (MODELS, SimParams, Trace,
                                 discover_azure_tables, simulate)

SCHEMA = "hydra-bench/v2"
DENSITY_ORDER = ("hydra-cluster", "hydra-pool", "hydra")
# per-model metrics carried into the artifact (summary-schema keys)
MODEL_KEYS = ("requests", "p50_s", "p99_s", "cold_runtime", "cold_isolate",
              "warm_isolate", "mean_mem_mb", "peak_mem_mb", "mean_runtimes",
              "pool_claims", "transfers", "dropped", "ops_per_gb_s")
# counters may legitimately be zero; these must be finite AND positive
POSITIVE_KEYS = ("requests", "p99_s", "mean_mem_mb", "ops_per_gb_s")


def git_sha() -> str:
    try:
        out = subprocess.run(["git", "rev-parse", "HEAD"],
                             capture_output=True, text=True, timeout=10,
                             cwd=os.path.dirname(os.path.abspath(__file__)))
        if out.returncode == 0:
            return out.stdout.strip()
    except OSError:
        pass
    return "unknown"


def _sha256(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 20), b""):
            h.update(block)
    return h.hexdigest()


def build_artifact(trace_file: str = AZURE_SAMPLE, calibration: str = None,
                   target_rps: float = None, max_minutes: int = None,
                   seed: int = 0, top_k: int = None, select: str = "top",
                   chunk_rows: int = 4096, gateway_smoke: bool = False,
                   gateway_compress: float = 120.0) -> dict:
    """Run the full-model streaming sweep (plus the optional live
    gateway leg) and assemble the artifact document. Raises
    ``ValueError`` for an unusable trace/window — the caller owns the
    clean-exit contract."""
    params = SimParams(**AZURE_PARAMS)
    if calibration:
        params = apply_calibration(params, calibration)
    trace = Trace.stream_azure(trace_file,
                               **discover_azure_tables(trace_file),
                               target_rps=target_rps,
                               max_minutes=max_minutes, seed=seed,
                               top_k=top_k, select=select,
                               chunk_rows=chunk_rows)
    models = {}
    for m in MODELS:
        s = simulate(trace, m, params).summary()
        models[m] = {k: s[k] for k in MODEL_KEYS}
    density = {m: models[m]["ops_per_gb_s"] for m in DENSITY_ORDER}
    provenance = trace.describe()      # exact: the sweep iterated fully
    provenance["path"] = os.path.basename(trace_file)
    provenance["sha256"] = _sha256(trace_file)

    doc = {
        "schema": SCHEMA,
        "git_sha": git_sha(),
        "trace": provenance,
        "params": dict(AZURE_PARAMS),
        "streaming": {"chunk_rows": chunk_rows,
                      "peak_buffered": trace.peak_buffered},
        "models": models,
        "density_ordering": {
            "order": list(DENSITY_ORDER),
            "values": density,
            "holds": density["hydra-cluster"] >= density["hydra-pool"]
            >= density["hydra"],
        },
        "gateway": _gateway_leg(trace_file, seed, gateway_compress)
        if gateway_smoke else None,
    }
    return doc


def _gateway_leg(trace_file: str, seed: int, compress: float) -> dict:
    """One thinned live replay through the real gateway stack (the CI
    gateway-smoke regime), reduced to the artifact's fixed key set."""
    from repro.gateway import load_trace, run_validation

    trace = load_trace(trace_file, target_rps=2.0, max_minutes=10,
                       seed=seed)
    # attribute=True traces every request of the live leg, so the
    # artifact carries per-phase latency columns (hydra-bench/v2) and
    # the measured dominant phase of the p99 tail
    report = run_validation(trace, compress=compress, pool_size=4,
                            attribute=True)
    live, sim = report["live"], report["sim"]
    extras = report.get("extras") or {}
    overhead = extras.get("request_overhead_ms") or {}
    exe = extras.get("exe_cache") or {}
    tracing = extras.get("tracing") or {}
    # fixed tracing vocabulary (Tracer.summary emits every key, None
    # when a phase never fired) -> run-stable key shape for the drift
    # gate; wall milliseconds
    phases = {name: {"p50_ms": s.get("p50_ms"), "p99_ms": s.get("p99_ms")}
              for name, s in (tracing.get("phases") or {}).items()}
    att = (report.get("attribution") or {}).get("p99") or {}
    return {
        "compress": compress,
        "requests": live["requests"],
        "p99_s": live["p99_s"],
        "cold_runtime": live["cold_runtime"],
        "pool_claims": live["pool_claims"],
        "dropped": live["dropped"],
        # per-request gateway overhead (latency - emulated duration) in
        # WALL ms — the request-path cost this repo's slab allocator +
        # compile caches keep flat; the CI overhead budget gates on the
        # bench_hotpath twin of this number
        "request_overhead_ms": {"mean": overhead.get("mean"),
                                "p99": overhead.get("p99")},
        "exe_compiles": exe.get("compiles"),
        "exe_disk_hits": exe.get("disk_hits"),
        "exe_cache_hits": exe.get("cache_hits"),
        # hydra-bench/v2: per-phase wall-ms latency columns from a
        # fully-sampled request trace of the smoke replay, plus the
        # measured dominant phase of the p99 tail (docs/observability.md)
        "phases": phases,
        "p99_dominant_phase": att.get("dominant"),
        "sim_p99_s": sim["p99_s"],
        "sim_cold_runtime": sim["cold_runtime"],
        "cold_within_tolerance": report["gates"]["cold_runtime"]["passed"],
        "p99_within_tolerance": report["gates"]["p99_s"]["passed"],
    }


# ---------------------------------------------------------------------------
def _key_shape(doc, prefix: str = "") -> set:
    """The recursive key structure of a JSON document — what schema
    drift is measured against. Leaf values (and list contents) don't
    contribute; a dict turning into a scalar/null or keys
    appearing/disappearing does."""
    shape = set()
    if isinstance(doc, dict):
        for k, v in sorted(doc.items()):
            shape.add(f"{prefix}{k}")
            shape |= _key_shape(v, f"{prefix}{k}.")
    return shape


def validate_artifact(doc: dict) -> list:
    """Internal consistency errors (empty list = valid): schema tag,
    required sections, finite/positive metrics for every model, the
    density ordering actually holding."""
    errors = []
    if doc.get("schema") != SCHEMA:
        errors.append(f"schema: expected {SCHEMA!r}, "
                      f"got {doc.get('schema')!r}")
    for section in ("git_sha", "trace", "params", "streaming", "models",
                    "density_ordering"):
        if section not in doc:
            errors.append(f"missing section: {section}")
    models = doc.get("models") or {}
    missing = [m for m in MODELS if m not in models]
    if missing:
        errors.append(f"models missing from sweep: {missing}")
    for m, metrics in models.items():
        for k in MODEL_KEYS:
            v = metrics.get(k)
            if not isinstance(v, (int, float)) or not math.isfinite(v):
                errors.append(f"models.{m}.{k}: non-finite {v!r}")
            elif k in POSITIVE_KEYS and v <= 0:
                errors.append(f"models.{m}.{k}: expected > 0, got {v!r}")
    ordering = doc.get("density_ordering") or {}
    if not ordering.get("holds", False):
        errors.append(f"density ordering violated: "
                      f"{ordering.get('values')}")
    trace = doc.get("trace") or {}
    if not trace.get("invocations"):
        errors.append("trace.invocations: zero invocations replayed")
    streaming = doc.get("streaming") or {}
    peak = streaming.get("peak_buffered", 0)
    n = trace.get("invocations") or 0
    if peak and n and peak > n:
        errors.append(f"streaming.peak_buffered={peak} exceeds "
                      f"invocations={n}")
    gateway = doc.get("gateway")
    if gateway is not None:
        for k in ("mean", "p99"):
            v = (gateway.get("request_overhead_ms") or {}).get(k)
            if not isinstance(v, (int, float)) or not math.isfinite(v) \
                    or v < 0:
                errors.append(
                    f"gateway.request_overhead_ms.{k}: expected finite "
                    f">= 0, got {v!r}")
        # v2: the per-phase columns must carry the FULL tracing
        # vocabulary (unfired phases are null, never absent) and the
        # end-to-end 'total' phase must have actually been observed
        phases = gateway.get("phases") or {}
        missing_phases = [k for k in SUMMARY_KEYS if k not in phases]
        if missing_phases:
            errors.append(f"gateway.phases missing vocabulary entries: "
                          f"{missing_phases}")
        total_p99 = (phases.get("total") or {}).get("p99_ms")
        if not isinstance(total_p99, (int, float)) \
                or not math.isfinite(total_p99) or total_p99 <= 0:
            errors.append(f"gateway.phases.total.p99_ms: expected finite "
                          f"> 0, got {total_p99!r}")
    return errors


def check_against(new: dict, committed: dict) -> list:
    """CI gate: schema drift between the regenerated and committed
    documents, or a density-ordering regression. Values may move; the
    contract may not."""
    errors = []
    if new.get("schema") != committed.get("schema"):
        errors.append(f"schema drift: committed {committed.get('schema')!r}"
                      f" vs regenerated {new.get('schema')!r}")
    new_shape, old_shape = _key_shape(new), _key_shape(committed)
    for key in sorted(old_shape - new_shape):
        errors.append(f"schema drift: key disappeared: {key}")
    for key in sorted(new_shape - old_shape):
        errors.append(f"schema drift: key appeared: {key}")
    was = (committed.get("density_ordering") or {}).get("holds", False)
    now = (new.get("density_ordering") or {}).get("holds", False)
    if was and not now:
        errors.append(
            f"density ordering regression: committed artifact held "
            f"cluster >= pool >= hydra, regenerated does not: "
            f"{(new.get('density_ordering') or {}).get('values')}")
    return errors


def write_artifact(doc: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the artifact JSON here (validated first; "
                         "nothing is written on a validation failure)")
    ap.add_argument("--check-against", default=None, metavar="PATH",
                    help="committed BENCH_trace.json to diff the "
                         "regenerated document against (schema drift / "
                         "density-ordering regression fail)")
    ap.add_argument("--trace-file", default=AZURE_SAMPLE,
                    help="Azure Functions 2019-format invocations CSV "
                         "(default: the bundled sample)")
    ap.add_argument("--calibration", default=None,
                    help="hydra-calibration/v1 JSON overriding the paper "
                         "constants for the sweep")
    ap.add_argument("--target-rps", type=float, default=None,
                    help="deterministically thin the trace to this mean "
                         "rps before the sweep")
    ap.add_argument("--max-minutes", type=int, default=None,
                    help="sweep only the first N minutes of the trace")
    ap.add_argument("--seed", type=int, default=0,
                    help="thinning/expansion seed")
    ap.add_argument("--top-k", type=int, default=None,
                    help="keep only K function rows (see --select)")
    ap.add_argument("--select", default="top", choices=("top", "stratified"),
                    help="top-K policy: K busiest rows, or one seeded "
                         "pick per popularity stratum")
    ap.add_argument("--chunk-rows", type=int, default=4096,
                    help="CSV ingestion chunk size (rows)")
    ap.add_argument("--gateway-smoke", action="store_true",
                    help="also run one thinned live replay through the "
                         "real gateway stack and record its leg")
    ap.add_argument("--gateway-compress", type=float, default=None,
                    help="wall-clock compression for the gateway leg "
                         "(default 120)")
    args = ap.parse_args(argv)

    if args.gateway_compress is not None and not args.gateway_smoke:
        print("bench_artifact: --gateway-compress requires --gateway-smoke",
              file=sys.stderr)
        return 2
    if not args.out and not args.check_against:
        print("bench_artifact: nothing to do (pass --out and/or "
              "--check-against)", file=sys.stderr)
        return 2
    if not os.path.isfile(args.trace_file):
        print(f"bench_artifact: trace file not found: {args.trace_file}",
              file=sys.stderr)
        return 2

    try:
        doc = build_artifact(args.trace_file, calibration=args.calibration,
                             target_rps=args.target_rps,
                             max_minutes=args.max_minutes, seed=args.seed,
                             top_k=args.top_k, select=args.select,
                             chunk_rows=args.chunk_rows,
                             gateway_smoke=args.gateway_smoke,
                             gateway_compress=args.gateway_compress
                             or 120.0)
    except ValueError as e:
        print(f"bench_artifact: {e}", file=sys.stderr)
        return 2

    errors = validate_artifact(doc)
    if args.check_against:
        try:
            with open(args.check_against) as f:
                committed = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"bench_artifact: cannot read committed artifact "
                  f"{args.check_against}: {e}", file=sys.stderr)
            return 2
        errors += check_against(doc, committed)

    for e in errors:
        print(f"# FAIL {e}", file=sys.stderr)
    if errors:
        return 1
    if args.out:
        write_artifact(doc, args.out)
        print(f"bench_artifact: wrote {args.out} "
              f"(git {doc['git_sha'][:12]})")
    else:
        print("bench_artifact: regenerated document matches the committed "
              "schema; density ordering holds")
    return 0


if __name__ == "__main__":
    from repro.core.executable_cache import configure_compile_cache
    configure_compile_cache()
    raise SystemExit(main())
