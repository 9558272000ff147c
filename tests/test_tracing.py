"""Request tracing (repro.core.tracing): the phase-conservation
invariant on a live replay, thread-safety of the span pipeline,
deterministic head sampling, Chrome trace-event schema validation, the
anomaly flight recorder's bounds, and the near-zero disabled path."""
import json
import threading
import time

from repro.core.platform import HydraPlatform, PlatformParams
from repro.core.tracing import (ARENA_KINDS, NULL_TRACE, PHASES,
                                SUMMARY_KEYS, FlightRecorder, PhaseBreakdown,
                                RequestTrace, Tracer, chrome_trace,
                                trace_now, validate_chrome)
from repro.core.traces import Invocation, Trace
from repro.gateway import ReplayConfig, replay_trace

MB = 1 << 20


def make_trace(n=24, gap_s=0.5, duration_s=0.2, n_fns=4, n_tenants=2,
               mem_mb=80):
    invs = tuple(
        Invocation(t=i * gap_s, fid=i % n_fns, tenant=(i % n_fns) % n_tenants,
                   duration_s=duration_s, mem_bytes=mem_mb * MB)
        for i in range(n))
    return Trace(invocations=invs, source="synthetic")


def traced_replay(trace, tracer, compress=30.0, **cfg_kw):
    plat = HydraPlatform(PlatformParams(
        pool_size=1, runtime_budget_bytes=64 * MB,
        arena_ttl_s=10.0 / compress, n_workers=2))
    try:
        return replay_trace(trace, plat,
                            ReplayConfig(compress=compress, n_workers=4,
                                         **cfg_kw),
                            tracer=tracer)
    finally:
        plat.shutdown()


# ---------------------------------------------------------------------------
# conservation on a live replay
# ---------------------------------------------------------------------------
def test_live_replay_phases_conserve_and_export_validates():
    tracer = Tracer(1.0, seed=0)
    res, extras = traced_replay(make_trace(n=16, gap_s=0.4), tracer)
    traces = tracer.traces()
    assert len(traces) == tracer.summary()["finished"] >= 1
    for t in traces:
        # per-request conservation: spans + unattributed == total + overlap
        phase_sum = sum(t["phases"].values())
        assert abs(phase_sum - t["total_s"] - t["overlap_s"]) < 1e-6
        # every span inside the request window, every name in the registry
        t_end = t["t0"] + t["total_s"] + 1e-4
        for sp in t["spans"]:
            assert sp["name"] in PHASES
            assert t["t0"] - 1e-4 <= sp["t0"] <= sp["t1"] <= t_end
    # the exported Chrome doc passes its own checker (schema + epsilon)
    doc = chrome_trace(traces, meta={"test": True})
    assert validate_chrome(doc) == []
    assert doc["otherData"]["schema"] == "hydra-trace/v1"
    # served requests all carry the core invoke phases
    ok = [t for t in traces if t["status"] == "ok"]
    assert ok
    for t in ok:
        names = {sp["name"] for sp in t["spans"]}
        assert {"admission", "queue_wait", "arena_acquire",
                "compute", "body"} <= names
    # the replay extras surface the aggregate with the full vocabulary
    assert set(extras["tracing"]["phases"]) == set(SUMMARY_KEYS)


def test_phase_breakdown_counts_overlap_once():
    spans = [("compute", 1.0, 2.0, None), ("dispatch", 1.5, 2.5, None)]
    bd = PhaseBreakdown.compute(spans, total_s=3.0)
    assert abs(bd.overlap_s - 0.5) < 1e-12          # 0.5s double-counted
    assert abs(bd.phases["unattributed"] - 1.5) < 1e-12   # 3.0 - covered 1.5
    assert bd.conservation_error_s() < 1e-12


# ---------------------------------------------------------------------------
# thread safety: concurrent requests never interleave spans
# ---------------------------------------------------------------------------
def test_multithread_hammer_no_cross_request_interleave():
    tracer = Tracer(1.0, seed=0, max_traces=10_000)
    n_threads, n_reqs = 8, 50
    start = threading.Barrier(n_threads)
    errors = []

    def worker(wid):
        try:
            start.wait(timeout=10.0)
            for i in range(n_reqs):
                ctx = tracer.start_request(f"fn-{wid}", tenant=f"t{wid}")
                with ctx.span("compute") as sp:
                    sp.set(worker=wid, i=i)
                ctx.add_span("queue_wait", ctx.t0, trace_now())
                ctx.finish("ok")
        except Exception as e:      # pragma: no cover - failure reporting
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30.0)
    assert not errors, errors

    traces = tracer.traces()
    assert len(traces) == n_threads * n_reqs
    assert len({t["trace_id"] for t in traces}) == len(traces)  # unique ids
    for t in traces:
        # exactly this request's two spans — nothing leaked across
        assert [sp["name"] for sp in t["spans"]] == ["compute", "queue_wait"]
        wid = int(t["fid"].split("-")[1])
        assert t["spans"][0]["attrs"]["worker"] == wid
        assert abs(sum(t["phases"].values())
                   - t["total_s"] - t["overlap_s"]) < 1e-6
    s = tracer.summary()
    assert s["requests"] == s["sampled"] == s["finished"] == len(traces)


# ---------------------------------------------------------------------------
# deterministic head sampling
# ---------------------------------------------------------------------------
def test_sampling_is_deterministic_under_fixed_seed():
    a = Tracer(0.3, seed=42)
    b = Tracer(0.3, seed=42)
    decisions = [a.would_sample(i) for i in range(2000)]
    assert decisions == [b.would_sample(i) for i in range(2000)]
    # the live path takes exactly the precomputed decisions, in order
    live = [a.start_request("f").sampled for _ in range(2000)]
    assert live == decisions
    # rate is honoured statistically, and a different seed re-deals
    frac = sum(decisions) / len(decisions)
    assert 0.2 < frac < 0.4
    assert decisions != [Tracer(0.3, seed=43).would_sample(i)
                         for i in range(2000)]


def test_sampling_edge_rates():
    off = Tracer(0.0)
    assert off.start_request("f") is NULL_TRACE
    assert not off.would_sample(0)
    assert off.summary()["requests"] == 0     # rate 0 skips even counting
    on = Tracer(1.0)
    assert all(on.would_sample(i) for i in range(100))


def test_null_trace_is_inert():
    ctx = NULL_TRACE
    assert not ctx.sampled
    with ctx.span("compute") as sp:
        sp.set(kind="reuse")                  # all no-ops, no state
    ctx.add_span("queue_wait", 0.0, 1.0)
    ctx.finish("ok")
    # hydralint: disable=HL008 — deliberately bare: asserting the no-op
    # singleton, not timing a phase
    assert ctx.span("compute") is ctx.span("body")


# ---------------------------------------------------------------------------
# Chrome export schema validation
# ---------------------------------------------------------------------------
def _one_trace_doc():
    tracer = Tracer(1.0)
    ctx = tracer.start_request("f1", "t0")
    with ctx.span("compute"):
        time.sleep(0.002)
    ctx.finish("ok")
    return chrome_trace(tracer.traces())


def test_validate_chrome_accepts_good_and_rejects_corrupt():
    doc = _one_trace_doc()
    assert validate_chrome(doc) == []
    assert json.loads(json.dumps(doc)) == doc      # JSON-serializable

    assert validate_chrome({"foo": 1})             # traceEvents missing
    assert validate_chrome({"traceEvents": []})    # no request tracks

    bad_name = json.loads(json.dumps(doc))
    bad_name["traceEvents"][1]["name"] = "made_up_phase"
    assert any("unknown span name" in e for e in validate_chrome(bad_name))

    bad_sum = json.loads(json.dumps(doc))
    for ev in bad_sum["traceEvents"]:
        if ev["name"] == "compute":
            ev["dur"] += 50_000.0                  # +50ms breaks conservation
    assert any("conservation" in e for e in validate_chrome(bad_sum))

    two_reqs = json.loads(json.dumps(doc))
    two_reqs["traceEvents"].append(dict(two_reqs["traceEvents"][0]))
    assert any("request events" in e for e in validate_chrome(two_reqs))

    bad_ph = json.loads(json.dumps(doc))
    bad_ph["traceEvents"][0]["ph"] = "B"
    assert any("ph=" in e for e in validate_chrome(bad_ph))


def test_chrome_cli_checker(tmp_path, capsys):
    from repro.core.tracing import main
    good = tmp_path / "spans.json"
    good.write_text(json.dumps(_one_trace_doc()))
    assert main(["--check", str(good)]) == 0
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert main(["--check", str(bad)]) == 2
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"traceEvents": []}))
    assert main(["--check", str(empty)]) == 1


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
def test_flight_recorder_ring_is_bounded_and_dumps_jsonl(tmp_path):
    fl = FlightRecorder(str(tmp_path), ring=8, max_dumps=2)
    tracer = Tracer(1.0, flight=fl)
    tracer.set_metrics_provider(lambda: {"runtimes": 3})
    for i in range(50):
        ctx = tracer.start_request(f"fn{i}")
        with ctx.span("compute"):
            pass
        ctx.finish("ok")
    assert len(fl) == 8                         # ring kept only the last 8

    trigger = tracer.start_request("victim")
    trigger.finish("slo_timeout")
    path = tracer.anomaly("slo_violation", fid="victim", ctx=trigger)
    assert path is not None
    lines = [json.loads(l) for l in open(path)]
    header, traces = lines[0], lines[1:]
    assert header["schema"] == "hydra-flight/v1"
    assert header["anomaly"] == "slo_violation"
    assert header["fid"] == "victim"
    assert header["metrics"] == {"runtimes": 3}
    assert header["trigger"]["fid"] == "victim"
    assert header["n_traces"] == len(traces) == 8
    assert traces[-1]["fid"] == "victim"        # newest ring entry

    # dump cap: the 3rd anomaly is counted but not written
    assert tracer.anomaly("oom_give_up") is not None
    assert tracer.anomaly("oom_give_up") is None
    assert fl.dumps == 2 and fl.dropped == 1
    s = tracer.summary()
    assert s["anomalies"] == {"slo_violation": 1, "oom_give_up": 2}
    assert s["flight"] == {"recorded": 8, "dumps": 2, "dump_cap_dropped": 1}


def test_gateway_slo_drop_fires_flight_dump(tmp_path):
    fl = FlightRecorder(str(tmp_path / "flight"))
    tracer = Tracer(1.0, seed=0, flight=fl)
    # 2x-compressed replay with an SLO far tighter than the service time:
    # most requests drop at pickup, each firing an slo_violation anomaly
    traced_replay(make_trace(n=12, gap_s=0.05, duration_s=1.0),
                  tracer, compress=60.0, slo_timeout_s=0.5)
    s = tracer.summary()
    assert s["anomalies"].get("slo_violation", 0) >= 1
    dumps = sorted((tmp_path / "flight").glob("flight-*.jsonl"))
    assert dumps
    header = json.loads(dumps[0].read_text().splitlines()[0])
    assert header["schema"] == "hydra-flight/v1"
    assert "metrics" in header                   # fleet snapshot embedded


# ---------------------------------------------------------------------------
# aggregation + attribution
# ---------------------------------------------------------------------------
def test_summary_vocabulary_is_fixed_and_arena_kinds_split():
    tracer = Tracer(1.0)
    for kind, secs in (("reuse", 0.001), ("zeroed", 0.002), ("cold", 0.01)):
        ctx = tracer.start_request("f")
        t0 = trace_now()
        ctx.add_span("arena_acquire", t0, t0 + secs, kind=kind)
        ctx.finish("ok")
    s = tracer.summary()
    assert set(s["phases"]) == set(SUMMARY_KEYS)
    for kind in ARENA_KINDS:
        assert s["phases"][f"arena_acquire.{kind}"]["count"] == 1
    assert s["phases"]["arena_acquire"]["count"] == 3
    assert s["phases"]["compute"]["count"] == 0          # fixed keys, None
    assert s["phases"]["compute"]["p99_ms"] is None


def test_attribution_names_dominant_phase():
    tracer = Tracer(1.0)
    for i in range(20):
        ctx = tracer.start_request(f"f{i}")
        t0 = trace_now()
        ctx.add_span("queue_wait", t0, t0 + 0.001)
        ctx.add_span("body", t0 + 0.001, t0 + 0.099)   # body must not win
        if i == 19:
            # one genuinely slow cold request: the p99 tail is selected
            # on wall total_s (t0 -> finish), so the dominating phase
            # must hold the request open for real time
            with ctx.span("restore"):
                time.sleep(0.05)
        ctx.finish("ok")
    att = tracer.attribution()
    assert att["requests"] == 20
    assert att["p99"]["dominant"] == "restore"
    assert att["cold"]["n"] == 1
    assert att["cold"]["dominant"] == "restore"


def test_export_window_is_bounded():
    tracer = Tracer(1.0, max_traces=16)
    for i in range(40):
        ctx = tracer.start_request(f"f{i}")
        ctx.finish("ok")
    assert len(tracer.traces()) == 16
    s = tracer.summary()
    assert s["export_window_dropped"] == 24
    assert s["finished"] == 40                 # aggregation saw everything


def test_sampled_span_is_on_the_profiler_clock(tmp_path):
    import glob
    import os

    import jax
    tracer = Tracer(sample_rate=1.0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        ctx = tracer.start_request("f0", "t0")
        try:
            with ctx.span("compute"):
                pass
            ctx.add_span("dispatch", 0.0, 0.0)    # host clock only
        finally:
            ctx.finish()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                       recursive=True)
    names = [e.name for plane in
             jax.profiler.ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events
             if e.name.startswith("hydra.")]
    assert names == ["hydra.compute"]
    assert [s[0] for s in ctx.spans] == ["compute", "dispatch"]
