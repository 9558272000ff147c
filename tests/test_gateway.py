"""Gateway subsystem: open-loop wall-clock replay against the real
stack — admission control (bounded queues, token buckets), SLO
timeouts, the platform autoscaler, the cluster balancer (mid-burst
snapshot migration), SimResult-schema recording, the sim-vs-live
validation harness, and the gateway -> calibration -> sim round trip."""
import time

import jax
import pytest

from repro.core.calibrate import (CALIBRATABLE_FIELDS, apply_calibration,
                                  calibration_from_replay)
from repro.core.platform import HydraPlatform, PlatformParams
from repro.core.sim import SimParams, simulate
from repro.core.sim.engine import SimResult
from repro.core.traces import Invocation, Trace
from repro.gateway import (Autoscaler, ClusterBalancer, Gateway,
                           GatewayParams, LoadGenerator, Recorder,
                           ReplayConfig, replay_trace, run_validation,
                           sim_params_for_live, wrap_target)
from repro.gateway.replay import build_workload
from repro.gateway.validate import gate, round_trip_check
from tools.hydralint import leaksan, locksan

MB = 1 << 20


def make_trace(n=24, gap_s=0.5, duration_s=0.2, n_fns=4, n_tenants=2,
               mem_mb=80):
    invs = tuple(
        Invocation(t=i * gap_s, fid=i % n_fns, tenant=(i % n_fns) % n_tenants,
                   duration_s=duration_s, mem_bytes=mem_mb * MB)
        for i in range(n))
    return Trace(invocations=invs, source="synthetic")


def small_platform(compress=30.0, pool=1, budget=64 * MB):
    return HydraPlatform(PlatformParams(
        pool_size=pool, runtime_budget_bytes=budget,
        arena_ttl_s=10.0 / compress, n_workers=2))


# ---------------------------------------------------------------------------
def test_replay_emits_simresult_schema_and_full_accounting():
    # locksan: the full replay stack (gateway workers, recorder sampler,
    # platform janitor) runs under the lock-order sanitizer — the platform
    # is built inside the patch so every lock it creates is wrapped.
    # leaksan: every arena/runtime/trace claim made by the replay must be
    # returned by the time the platform finishes shutting down.
    with locksan.sanitized(), leaksan.sanitized():
        trace = make_trace(n=24, gap_s=0.4)
        plat = small_platform(compress=30.0)
        try:
            res, extras = replay_trace(
                trace, plat, ReplayConfig(compress=30.0, n_workers=4))
        finally:
            plat.shutdown()
    assert isinstance(res, SimResult)
    # EXACT summary schema parity with the simulator
    assert set(res.summary()) == set(SimResult(model="x").summary())
    s = res.summary()
    assert s["requests"] + s["dropped"] == len(trace)
    assert s["requests"] > 0
    assert all(l > 0 for l in res.latencies)
    # the pool served the first placement: a claim, never an inline boot
    assert s["pool_claims"] >= 1
    assert s["cold_runtime"] == 0
    assert res.mem_samples and res.mem_samples[-1][1] > 0
    assert extras["submitted"] == len(trace)
    assert extras["drained"]
    # per-request overhead (latency - emulated duration) in wall ms: one
    # sample per served request, and the emulated sleep never undershoots
    ovh = extras["request_overhead_ms"]
    assert ovh["count"] == s["requests"]
    assert ovh["mean"] > 0.0
    assert ovh["p99"] >= 0.0
    # fleet compile + slab counters surface through the adapter
    exe = extras["exe_cache"]
    assert exe["entries"] >= 1
    assert {"compiles", "disk_hits", "cache_hits",
            "xla_cache_dir"} <= set(exe)
    # the persistent compile cache is process-global, set up only by an
    # entry point (configure_compile_cache), never by building a stack
    assert exe["xla_cache_dir"] == (jax.config.jax_compilation_cache_dir
                                    or None)
    assert {"reuse", "zeroed"} == set(extras["slab"])


def test_replay_against_cluster_target():
    from repro.core.cluster import ClusterParams, HydraCluster
    trace = make_trace(n=16, gap_s=0.4, n_fns=4, n_tenants=4)
    cluster = HydraCluster(ClusterParams(
        n_nodes=2, node_memory_bytes=256 * MB,
        platform=PlatformParams(pool_size=1, runtime_budget_bytes=64 * MB,
                                arena_ttl_s=10.0 / 30.0)))
    try:
        res, extras = replay_trace(trace, cluster,
                                   ReplayConfig(compress=30.0, n_workers=4))
    finally:
        cluster.shutdown()
    s = res.summary()
    assert res.model == "live-cluster"
    assert s["n_nodes"] == 2
    assert s["requests"] + s["dropped"] == len(trace)
    assert s["requests"] > 0


# ---------------------------------------------------------------------------
def _gateway_fixture(trace, plat, params):
    adapter = wrap_target(plat)
    workload = build_workload(adapter, ReplayConfig(compress=params.compress))
    workload.register_all(trace, adapter)
    recorder = Recorder(adapter, compress=params.compress)
    gw = Gateway(adapter, workload, params, recorder)
    return gw, recorder


def test_bounded_queue_rejects_overflow():
    # 1 worker busy sleeping 0.5s wall per request; depth 2 -> the burst
    # overflows the tenant queue and is rejected at the door
    trace = make_trace(n=8, gap_s=0.0, duration_s=0.5, n_fns=1, n_tenants=1)
    plat = small_platform(compress=1.0)
    gw, recorder = _gateway_fixture(
        trace, plat, GatewayParams(n_workers=1, queue_depth=2, compress=1.0))
    try:
        gw.start()
        accepted = sum(gw.submit(inv) for inv in trace)
        assert accepted < len(trace)
        assert gw.drain(timeout_s=30.0)
    finally:
        gw.stop()
        plat.shutdown()
    extras = recorder.extras()
    assert extras["drops"].get("rejected", 0) >= 1
    res = recorder.finish()
    assert len(res.latencies) + res.dropped == len(trace)


def test_slo_timeout_drops_stale_requests():
    # sub-ms SLO (in trace seconds) with a single busy worker: queued
    # requests expire before they are served
    trace = make_trace(n=6, gap_s=0.0, duration_s=0.4, n_fns=1, n_tenants=1)
    plat = small_platform(compress=1.0)
    gw, recorder = _gateway_fixture(
        trace, plat, GatewayParams(n_workers=1, queue_depth=64,
                                   slo_timeout_s=0.05, compress=1.0))
    try:
        gw.start()
        for inv in trace:
            gw.submit(inv)
        assert gw.drain(timeout_s=30.0)
    finally:
        gw.stop()
        plat.shutdown()
    assert recorder.extras()["drops"].get("slo_timeout", 0) >= 1


def test_token_bucket_throttles_hot_tenant():
    trace = make_trace(n=10, gap_s=0.0, duration_s=0.01, n_fns=1,
                       n_tenants=1)
    plat = small_platform(compress=1.0)
    gw, recorder = _gateway_fixture(
        trace, plat, GatewayParams(n_workers=2, tenant_rate=0.001,
                                   tenant_burst=2.0, compress=1.0))
    try:
        gw.start()
        for inv in trace:
            gw.submit(inv)
        gw.drain(timeout_s=30.0)
    finally:
        gw.stop()
        plat.shutdown()
    drops = recorder.extras()["drops"]
    # burst of 2 admitted, the rest throttled by the per-tenant bucket
    assert drops.get("throttled", 0) >= len(trace) - 3


def test_unknown_function_rejected_at_door():
    plat = small_platform()
    gw, recorder = _gateway_fixture(make_trace(n=4), plat, GatewayParams())
    try:
        stranger = Invocation(t=0.0, fid=999, tenant=0, duration_s=0.1,
                              mem_bytes=MB)
        assert gw.submit(stranger) is False
    finally:
        gw.stop()
        plat.shutdown()
    assert recorder.extras()["drops"].get("unknown") == 1


# ---------------------------------------------------------------------------
def test_autoscaler_grows_on_burst_and_shrinks_when_idle():
    plat = small_platform(pool=1)
    try:
        scaler = Autoscaler(plat, pool_min=1, pool_max=4, cover_s=1.0)
        t = 1000.0
        for i in range(32):            # 100 req/s burst
            scaler.observe(t + i * 0.01)
        target = scaler.tick(t + 0.32)
        assert target == 4             # ceil(rate * cover) clamped to max
        assert plat.params.pool_size == 4
        assert scaler.resizes == 1
        # long idle: the rate estimate collapses, pool shrinks to floor
        target = scaler.tick(t + 500.0)
        assert target == 1
        assert plat.params.pool_size == 1
    finally:
        plat.shutdown()


def test_workload_arenas_capped_to_runtime_budget():
    # 8 GB trace functions against a 16 MB runtime: arenas are capped so
    # registration always admits (no HydraOOMError at the door)
    trace = make_trace(n=4, n_fns=2, mem_mb=8192)
    plat = HydraPlatform(PlatformParams(pool_size=1,
                                        runtime_budget_bytes=16 * MB))
    try:
        adapter = wrap_target(plat)
        workload = build_workload(adapter, ReplayConfig())
        n = workload.register_all(trace, adapter)
        assert n == 2
        for inv in trace[:2]:
            adapter.invoke(workload.name_for(inv), workload.args_for(inv))
    finally:
        plat.shutdown()


def test_loadgen_schedules_open_loop():
    class StubGateway:
        def __init__(self):
            self.walls = []

        def submit(self, inv, sched_wall=None):
            self.walls.append((time.monotonic(), sched_wall))
            return True

    trace = make_trace(n=5, gap_s=1.0)     # arrivals at 0, 1, 2, 3, 4
    stub = StubGateway()
    res = LoadGenerator(trace, stub, compress=20.0).run()
    assert res.submitted == res.accepted == 5
    # open loop: submit times track the compressed schedule (50ms gaps)
    gaps = [b - a for (a, _), (b, _) in zip(stub.walls, stub.walls[1:])]
    assert all(0.03 < g < 0.3 for g in gaps), gaps
    # intended schedule is preserved exactly
    scheds = [s for _, s in stub.walls]
    for i in range(1, 5):
        assert scheds[i] - scheds[0] == pytest.approx(i * 0.05, abs=1e-6)


def test_loadgen_absolute_schedule_under_sustained_lag():
    """Open-loop fidelity regression: when the submit path is slower
    than the compressed inter-arrival gap, the generator must keep
    scheduling against the ABSOLUTE trace timeline (t0 + t_i/compress),
    not against accumulated sleeps — otherwise the drift would re-time
    the tail of the trace and hide it from measured latency."""
    class SlowGateway:
        def __init__(self):
            self.scheds = []

        def submit(self, inv, sched_wall=None):
            time.sleep(0.003)          # 3ms submit >> 1ms arrival gap
            self.scheds.append(sched_wall)
            return True

    n = 40
    trace = make_trace(n=n, gap_s=0.05)     # 1ms wall gaps at compress 50
    stub = SlowGateway()
    t0 = time.monotonic()
    res = LoadGenerator(trace, stub, compress=50.0).run(t0)
    assert res.submitted == n
    # every intended schedule time is the absolute timeline, exactly —
    # lag is never folded into later requests' schedules
    for i, sched in enumerate(stub.scheds):
        assert sched - t0 == pytest.approx(i * 0.05 / 50.0, abs=1e-9)
    # the generator fell ~2ms further behind per request: that lag is
    # REPORTED (late count + max lag), charged to latency downstream
    assert res.late >= n // 2
    assert res.max_lag_s >= 0.020
    # and the worst lag is the cumulative one (the last submit), which
    # only exists if the schedule did not slip with the drift
    assert res.max_lag_s == pytest.approx(
        res.wall_s - 0.003 - (n - 1) * 0.001, abs=0.05)


# ---------------------------------------------------------------------------
def make_cluster(tmp_path, n_nodes=2, node_mb=256, compress=30.0):
    from repro.core.cluster import ClusterParams, HydraCluster
    return HydraCluster(ClusterParams(
        n_nodes=n_nodes, node_memory_bytes=node_mb * MB,
        snapshot_dir=str(tmp_path / "snap"),
        platform=PlatformParams(pool_size=1, runtime_budget_bytes=64 * MB,
                                arena_ttl_s=10.0 / compress)))


def test_cluster_balancer_migrates_mid_burst(tmp_path):
    """A tenant-skewed burst packs one node solid (colocation); the
    balancer must rebalance() mid-replay and the migrations must reach
    the live SimResult as transfers, matching the cluster's own
    accounting — the live analog of the hydra-cluster sim model's
    cross-node snapshot transfers."""
    invs = tuple(Invocation(t=i * 0.15, fid=i % 8, tenant=0,
                            duration_s=0.3, mem_bytes=80 * MB)
                 for i in range(48))
    trace = Trace(invocations=invs, source="synthetic")
    cluster = make_cluster(tmp_path)
    cfg = ReplayConfig(compress=30.0, n_workers=4,
                       balance_interval_s=0.05, balance_imbalance=0.01,
                       balance_min_queue=1)
    try:
        res, extras = replay_trace(trace, cluster, cfg)
        placement = cluster.placement()
    finally:
        cluster.shutdown()
    b = extras["balancer"]
    assert b["armed"]
    assert b["rebalances"] >= 1 and b["moves"] >= 1
    assert res.transfers >= 1
    # live SimResult transfer accounting == the cluster's own counters
    assert res.transfers == b["migrations"]
    assert b["transfer_bytes"] > 0 and b["transfer_s"] > 0
    # the burst really was rebalanced: both nodes host functions now
    assert len(set(placement.values())) == 2
    # mid-burst migration must not lose requests: every invocation is
    # served (mid-migration races are requeued, not errored)
    assert len(res.latencies) + res.dropped == len(trace)
    assert not extras["errors"]
    assert res.n_nodes == 2


def test_cluster_balancer_disarmed_without_snapshots():
    """No snapshot_dir -> migration is impossible; the balancer must
    stay disarmed instead of erroring every tick."""
    from repro.core.cluster import ClusterParams, HydraCluster
    cluster = HydraCluster(ClusterParams(
        n_nodes=2, node_memory_bytes=64 * MB,
        platform=PlatformParams(pool_size=1,
                                runtime_budget_bytes=32 * MB)))
    try:
        balancer = ClusterBalancer(cluster, None, imbalance=0.0)
        assert not balancer.armed
        assert balancer.tick() == 0
        assert balancer.errors == 0
    finally:
        cluster.shutdown()


def test_recorder_reports_real_node_count(tmp_path):
    """recorder.finish() must default to the adapter's REAL machine
    count: a 3-node cluster replay stamped n_nodes=1 would misread as
    3x the density of the sim's fleet-wide accounting."""
    cluster = make_cluster(tmp_path, n_nodes=3, node_mb=64)
    try:
        adapter = wrap_target(cluster)
        assert adapter.n_nodes == 3
        assert len(adapter.node_mem()) == 3
        rec = Recorder(adapter, compress=30.0)
        assert rec.finish().n_nodes == 3
        assert rec.finish(n_nodes=1).n_nodes == 1   # explicit override
    finally:
        cluster.shutdown()
    plat = small_platform()
    try:
        rec = Recorder(wrap_target(plat), compress=30.0)
        assert rec.finish().n_nodes == 1
    finally:
        plat.shutdown()


# ---------------------------------------------------------------------------
def test_latency_gates_scale_with_compression():
    # |live - sim| <= atol_wall * compress + rtol * sim, evaluated via
    # the shared gate() helper validate.py enforces with
    g = gate(10.0, 2.0, atol=0.25 * 60, rtol=1.0)
    assert g["passed"] and g["limit"] == pytest.approx(17.0)
    g = gate(40.0, 2.0, atol=0.25 * 60, rtol=1.0)
    assert not g["passed"]
    # the same wall-second divergence passes at higher compression
    # (startup is compress-amplified in trace time, and so is the atol)
    assert gate(40.0, 2.0, atol=0.25 * 240, rtol=1.0)["passed"]


def test_round_trip_check_requires_no_regression():
    live = {"cold_runtime": 10, "p99_s": 8.0}
    sim = {"cold_runtime": 2, "p99_s": 2.0}
    better = {"cold_runtime": 6, "p99_s": 5.0}
    worse = {"cold_runtime": 30, "p99_s": 2.0}
    rt = round_trip_check(live, sim, better)
    assert rt["passed"] and rt["p99_s"]["cal_delta"] == pytest.approx(3.0)
    rt = round_trip_check(live, sim, worse)
    assert not rt["passed"] and not rt["cold_runtime"]["passed"]
    # equal closeness is acceptance ("at least as close"), not failure
    assert round_trip_check(live, sim, dict(sim))["passed"]


def test_calibration_from_replay_scales_wall_costs():
    res = SimResult(model="live-platform", latencies=[0.1] * 4)
    extras = {"probe": {
        "compress": 120.0,
        "wall_costs": {
            "runtime_boot_s": {"count": 3, "sum": 0.06, "mean": 0.02},
            "pool_claim_s": {"count": 5, "sum": 5e-4, "mean": 1e-4},
            "register_s": {"count": 8, "sum": 0.008, "mean": 0.001},
            "arena.alloc_s": {"count": 9, "sum": 0.009, "mean": 0.001},
        },
        "rss": {"per_runtime_bytes": 48 * MB},
    }}
    doc = calibration_from_replay(res, extras)
    assert doc["schema"] == "hydra-calibration/v1"
    m = doc["measured"]
    assert set(m) <= set(CALIBRATABLE_FIELDS)
    # wall costs are trace-time scaled by compress...
    assert m["hydra_runtime_cold_s"] == pytest.approx(0.02 * 120)
    assert m["pool_refill_s"] == pytest.approx(0.02 * 120)
    assert m["pool_claim_s"] == pytest.approx(1e-4 * 120)
    assert m["fn_register_s"] == pytest.approx(0.001 * 120)
    assert m["isolate_cold_s"] == pytest.approx(0.001 * 120)
    # ...the measured boot covers the whole cold path (no microVM under it)
    assert m["vm_boot_s"] == 0.0
    # memory is reported in meta but NOT applied unless asked
    assert "hydra_runtime_base" not in m
    assert doc["meta"]["rss_per_runtime_bytes"] == 48 * MB
    m2 = calibration_from_replay(res, extras, include_memory=True)
    assert m2["measured"]["hydra_runtime_base"] == 48 * MB
    # the overlay round-trips through apply_calibration
    params = apply_calibration(SimParams(), m)
    assert params.hydra_runtime_cold_s == pytest.approx(2.4)
    with pytest.raises(ValueError):
        calibration_from_replay(res, {})     # no probe payload
    with pytest.raises(ValueError):
        calibration_from_replay(res, {"probe": {"compress": 120.0,
                                                "wall_costs": {}}})


def test_round_trip_reproduces_live_cold_starts():
    """The acceptance loop end-to-end on a seeded trace: replay live,
    derive the calibration from that very run, re-simulate with it —
    the calibrated sim must land within the validate gate of the live
    cold-start count and be at least as close as the uncalibrated sim
    on cold starts AND p99."""
    trace = Trace.synthetic(n_functions=8, n_tenants=4, duration_s=40.0,
                            mean_rps=1.5, seed=3)
    report = run_validation(trace, compress=40.0, pool_size=2,
                            n_workers=4, round_trip=True)
    assert report["ok"], report["failures"]
    assert report["round_trip"]["passed"]
    cal = report["calibration"]
    assert set(cal["measured"]) <= set(CALIBRATABLE_FIELDS)
    # feed the derived overlay back through apply_calibration + the sim
    # ourselves: the replayed cold-start count must be reproduced within
    # the validate gate (and match the report's calibrated sim)
    params = apply_calibration(
        sim_params_for_live(trace, pool_size=2,
                            live_runtime_budget=32 * MB,
                            mem_scale=1.0 / 64),
        cal["measured"])
    sim = simulate(trace, "hydra-pool", params)
    g = gate(report["live"]["cold_runtime"], sim.cold_runtime_starts,
             atol=8, rtol=1.0)
    assert g["passed"], g
    assert sim.cold_runtime_starts \
        == report["calibrated_sim"]["cold_runtime"]


# ---------------------------------------------------------------------------
def test_validation_report_on_synthetic_trace():
    trace = Trace.synthetic(n_functions=8, n_tenants=4, duration_s=40.0,
                            mean_rps=1.5, seed=3)
    report = run_validation(trace, compress=40.0, pool_size=2,
                            n_workers=4)
    assert set(report) >= {"live", "sim", "deltas", "tolerance",
                           "failures", "ok"}
    tol = report["tolerance"]
    assert tol["passed"], report["failures"]
    assert report["live"]["requests"] > 0
    assert report["sim"]["requests"] == len(trace)
    for k in ("cold_runtime", "p99_s", "requests"):
        assert k in report["deltas"]
    # live and sim agree that the pre-warmed pool absorbed the load
    assert abs(tol["cold_live"] - tol["cold_sim"]) <= tol["limit"]


# ---------------------------------------------------------------------------
# Tenant-sharded replay (ShardedLoadGenerator / shard_trace)
# ---------------------------------------------------------------------------
def test_shard_trace_partitions_by_tenant():
    from repro.gateway import shard_trace
    trace = make_trace(n=40, gap_s=0.25, n_fns=8, n_tenants=8)
    parts = [shard_trace(trace, 3, i) for i in range(3)]
    for i, part in enumerate(parts):
        assert all(inv.tenant % 3 == i for inv in part)
    merged = sorted((inv for p in parts for inv in p),
                    key=lambda i: (i.t, i.fid))
    assert merged == list(trace)
    # degenerate single-shard request returns the trace unchanged
    assert shard_trace(trace, 1, 0) is trace


def test_sharded_loadgen_conserves_and_keeps_tenant_fifo():
    """Acceptance: sharded replay conserves every invocation and keeps
    per-tenant arrival order (each tenant lives wholly in one shard)."""
    import threading

    from repro.gateway import ShardedLoadGenerator

    class CountingGateway:
        def __init__(self):
            self.lock = threading.Lock()
            self.seen = []

        def submit(self, inv, sched_wall=None):
            with self.lock:
                self.seen.append((inv.tenant, inv.t))
            return True

    trace = make_trace(n=40, gap_s=0.25, n_fns=8, n_tenants=8)
    stub = CountingGateway()
    res = ShardedLoadGenerator(trace, stub, compress=100.0,
                               n_shards=4).run()
    assert res.submitted == res.accepted == len(trace) == len(stub.seen)
    by_tenant = {}
    for tenant, t in stub.seen:
        by_tenant.setdefault(tenant, []).append(t)
    assert len(by_tenant) == 8
    for tenant, ts in by_tenant.items():
        assert ts == sorted(ts), f"tenant {tenant} out of order"


def test_sharded_replay_matches_single_worker_counters():
    """A real sharded replay of the bundled Azure sample serves the same
    workload as the unsharded run: full conservation, equal request
    counts within the admission-control tolerance."""
    import os
    SAMPLE = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                          "data", "azure_sample.csv")
    trace = Trace.from_azure(SAMPLE, target_rps=2.0, max_minutes=5)
    results = {}
    for shards in (1, 3):
        plat = small_platform(compress=120.0, pool=2, budget=256 * MB)
        try:
            res, extras = replay_trace(
                trace, plat,
                ReplayConfig(compress=120.0, n_workers=8, shards=shards))
        finally:
            plat.shutdown()
        s = res.summary()
        # conservation: every scheduled invocation is served or rejected
        assert extras["submitted"] == len(trace)
        assert s["requests"] + s["dropped"] == len(trace)
        results[shards] = s
    # both runs served everything (tiny load, no admission pressure), so
    # the counters agree exactly
    assert results[1]["requests"] == results[3]["requests"]
    assert results[1]["dropped"] == results[3]["dropped"] == 0
