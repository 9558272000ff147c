"""Device idle time charged to the program's host phases
(``chipbench/phases.py``): first on a hand-made trace with known answers,
then on an excerpt recorded on the chip with the program's spans
(``testdata/qwen2.5-3b.x2.chat-phases.json.gz``, written by
``excerpt.py``: 0.15 s of the traced window from 20 ms before an
admission), then the profile on disk that a run reads its spans from.
The metrics that read no program span keep the values they read on the
first recorded excerpt."""
import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import phases, runner, serving, spec
from chipbench import trace as tr
from test_chipbench_trace import _pd, _recorded, _view

DATA = Path(__file__).resolve().parent / "testdata"
CELL = "qwen2.5-3b.x2.chat"
EXCERPT = f"{CELL}-phases"


def _hand_made():
    """Window 0-100 ns; the device busy 10-20 and 50-60. The serving line
    (0) holds a step with an admission, a decode, a readback and a
    collection inside it; line 1 a janitor pass, a collection inside it,
    and a request span, which is not on the serving thread."""
    pd = _pd([
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["m(1)", 10, 10],
                                               ["m(2)", 50, 10]]},
            {"name": "XLA Ops", "events": [["a", 10, 10], ["b", 50, 10]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [
                ["chipbench.window", 0, 100],
                ["hydra.lm.step", 0, 90], ["hydra.lm.admit", 5, 20],
                ["hydra.lm.decode", 25, 5], ["hydra.lm.sync", 30, 50],
                ["hydra.gc", 70, 5], ["jit_lm_decode_sample", 25, 2]]},
            {"name": "python3", "events": [
                ["hydra.runtime.evict_idle", 40, 5], ["hydra.gc", 42, 2],
                ["hydra.compute", 85, 10]]}]}])
    t = tr.from_profile(pd)
    t.window = (0, 100)
    return t, phases.program_spans(pd)


def test_charge_by_hand():
    t, spans = _hand_made()
    assert [s.thread for s in spans if s.name == "hydra.compute"] == [1]
    assert phases.serving_thread(spans) == 0
    assert phases.idle_intervals(t) == [(0, 10), (20, 50), (60, 100)]
    got = phases.charge(t, spans)
    assert got == {
        "lm.step": 5 + 10,            # 0-5 and 80-90: no child open
        "lm.admit": 5 + 5,            # 5-10 and 20-25
        "lm.decode": 5,               # 25-30
        "lm.sync": 10 + 5 + 10 + 5,   # 30-40, 45-50, 60-70, 75-80
        "runtime.evict_idle": 2 + 1,  # background wins over the loop
        "gc": 2 + 5,                  # the innermost background phase
        "outside": 10,                # a request span off the loop
    }
    assert sum(got.values()) == t.window_ns - t.busy_ns() == 80


def test_background_wins_and_innermost_wins():
    pieces = phases.owners(_hand_made()[1], (0, 100))
    assert [p for p in pieces if p[0] <= 43 < p[1]][0][2] == "gc"
    assert [p for p in pieces if p[0] <= 41 < p[1]][0][2] \
        == "runtime.evict_idle"
    assert [p for p in pieces if p[0] <= 7 < p[1]][0][2] == "lm.admit"
    assert [p for p in pieces if p[0] <= 85 < p[1]][0][2] == "lm.step"
    # contiguous, and they cover the window
    assert pieces[0][0] == 0 and pieces[-1][1] == 100
    assert all(a[1] == b[0] for a, b in zip(pieces, pieces[1:]))


def test_no_spans_reads_nothing():
    t, _ = _hand_made()
    t.program_spans = []
    run = SimpleNamespace(trace=t)
    assert phases.charged(run) is None
    assert phases.started_in_window(run, "lm.admit") == []


def _excerpt():
    raw = json.loads(gzip.decompress((DATA / f"{EXCERPT}.json.gz")
                                     .read_bytes()))
    names = raw["names"]
    pd = _pd([{"name": p["name"], "lines": [
        {"name": ln["name"], "events": [[names[i], s, d]
                                        for i, s, d in ln["events"]]}
        for ln in p["lines"]]} for p in raw["planes"]])
    t = tr.from_profile(pd)
    t.window = tuple(raw["window"])
    t.program_spans = phases.program_spans(pd)
    n = max(int(k) for k in raw["steps"]) + 1
    steps = [serving.Step(0, 0.0, 0.0) for _ in range(n)]
    for k, v in raw["steps"].items():
        steps[int(k)].admitted, steps[int(k)].ctx = v["admitted"], v["ctx"]
    runner.attribute(t, steps)
    return t, steps


def test_recorded_excerpt_conserves_idle_time():
    t, _ = _excerpt()
    got = phases.charge(t, t.program_spans)
    idle = t.window_ns - t.busy_ns()
    assert idle > 0
    assert abs(sum(got.values()) - idle) <= 1.0
    assert got.get("lm.sync", 0) > 0 and got.get("lm.admit", 0) > 0


@pytest.mark.parametrize("metric", ["sync_idle_ms_per_decode",
                                    "admit_idle_ms_per_prefill"])
def test_recorded_excerpt_reads_the_phase_metrics(metric):
    t, steps = _excerpt()
    assert any(m.kind == "prefill" and m.step >= 0 for m in t.modules)
    cell, view = _view(CELL, t, steps)
    assert metric in {m["name"] for m in cell.per_layer}
    value = cell.reader(metric).read(view)
    assert value is not None and value > 0


def test_recorded_excerpt_reads_every_trace_metric():
    # the excerpt holds an admission, so the prefill metrics read too
    t, steps = _excerpt()
    cell, view = _view(CELL, t, steps)
    for m in cell.per_layer:
        if m["source"] not in ("device_trace", "program_span"):
            continue
        got = cell.reader(m["name"]).read(view)
        value = got["value"] if isinstance(got, dict) else got
        assert value is not None and value > 0, m["name"]
        if m["unit"] == "%":
            assert value <= 100.0, (m["name"], value)


# what each reader of the device trace read on the first recorded excerpt
# (decode steps only) before the program had spans
FIRST_EXCERPT = {
    "decode_step_ms": 33.43168333333333,
    "prefill_ms_per_ktok": None,
    "decode_mfu": 0.3494761903784609,
    "prefill_mfu": None,
    "decode_attention_roofline": 0.37259076990195505,
    "flash_attention_roofline": None,
    "device_idle_share": 10.496633333333339,
}


@pytest.mark.parametrize("metric", sorted(FIRST_EXCERPT))
def test_trace_readers_keep_their_values(metric):
    t, steps = _recorded(CELL)
    runner.attribute(t, steps)
    cell, view = _view(CELL, t, steps)
    got = cell.reader(metric).read(view)
    value = got["value"] if isinstance(got, dict) else got
    want = FIRST_EXCERPT[metric]
    assert value == (None if want is None else pytest.approx(want,
                                                             rel=1e-12))


def test_spans_from_the_profile_on_disk(tmp_path, monkeypatch):
    """A run's trace holds no program spans; they are read from the
    profile the run still holds, and only from the one whose window is
    the trace's."""
    import jax
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    trace_dir = tmp_path / "chipbench-trace-x"
    jax.profiler.start_trace(str(trace_dir))
    try:
        with jax.profiler.TraceAnnotation(serving.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("hydra.lm.step"):
                with jax.profiler.TraceAnnotation("hydra.lm.sync",
                                                  tokens=3):
                    pass
    finally:
        jax.profiler.stop_trace()
    t = tr.load(str(trace_dir), serving.WINDOW_SPAN)
    got = phases.spans_of(t)
    assert [s.name for s in got] == ["hydra.lm.step", "hydra.lm.sync"]
    assert got[0].thread == got[1].thread
    assert phases.spans_of(t) is got                # read once
    other = tr.load(str(trace_dir), serving.WINDOW_SPAN)
    other.window = (t.window[0], t.window[1] + 1e3)  # another run's window
    assert phases.spans_of(other) == []
    (trace_dir / "later.xplane.pb").write_bytes(b"not a profile")
    assert phases.spans_of(tr.load(str(trace_dir) + "/plugins",
                                   serving.WINDOW_SPAN)) == []


def test_cell_lists_the_phase_metrics():
    cell = spec.load_cell(CELL)
    got = {m["name"]: m for m in cell.per_layer}
    assert got["sync_idle_ms_per_decode"]["moves"] == "itl_p95_ms"
    assert got["admit_idle_ms_per_prefill"]["moves"] == "ttft_p90_ms"
