"""The reduction from a profiler trace to per-layer metrics: busy union,
idle share, kernel time by name, steps tied to the harness's spans, and
idle gaps named by the span open across them. First on a hand-made
trace with known answers, then on excerpts recorded from the chip
(``testdata/*.json.gz``: 0.15 s of each cell's traced window, with the
harness's step log; the qwen2.5-3b excerpt holds decode steps only, so
its prefill metrics must read nothing)."""
import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import kernels, runner, serving, spec
from chipbench import trace as tr

DATA = Path(__file__).resolve().parent / "testdata"


def _pd(planes):
    """A stand-in for ``jax.profiler.ProfileData``: planes of lines of
    events with ``name``, ``start_ns`` and ``duration_ns``."""
    def ev(name, start, dur):
        return SimpleNamespace(name=name, start_ns=start, duration_ns=dur)
    return SimpleNamespace(planes=[
        SimpleNamespace(name=p["name"], lines=[
            SimpleNamespace(name=ln["name"],
                            events=[ev(*e) for e in ln["events"]])
            for ln in p["lines"]])
        for p in planes])


def test_union_and_idle_by_hand():
    ops = [tr.Event("a", 0, 10), tr.Event("b", 5, 15), tr.Event("c", 20, 30)]
    assert tr.union_ns(ops) == 25
    pd = _pd([
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["m(1)", 0, 16],
                                               ["m(2)", 20, 10]]},
            {"name": "XLA Ops", "events": [["a", 0, 10], ["b", 5, 10],
                                           ["c", 20, 10]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [
                ["chipbench.window", 0, 40], ["chipbench.step.0", 0, 17],
                ["chipbench.idle", 17, 3], ["chipbench.step.1", 20, 20],
                ["not ours", 0, 40]]}]}])
    t = tr.from_profile(pd)
    t.window = (0, 40)
    assert t.busy_ns() == 25 and t.window_ns == 40
    assert [len(m.ops) for m in t.modules] == [2, 1]
    assert [s.name for s in t.spans][0] == "chipbench.window"
    gaps = tr.idle_gaps(t)
    assert gaps == [["chipbench.step.1", 10e-9], ["chipbench.idle", 5e-9]]


def _recorded(name):
    raw = json.loads(gzip.decompress((DATA / f"{name}.json.gz").read_bytes()))
    names = raw["names"]
    planes = [{"name": p["name"], "lines": [
        {"name": ln["name"], "events": [[names[i], s, d]
                                        for i, s, d in ln["events"]]}
        for ln in p["lines"]]} for p in raw["planes"]]
    t = tr.from_profile(_pd(planes))
    t.window = tuple(raw["window"])
    n = max(int(k) for k in raw["steps"]) + 1
    steps = [serving.Step(0, 0.0, 0.0) for _ in range(n)]
    for k, v in raw["steps"].items():
        steps[int(k)].admitted, steps[int(k)].ctx = v["admitted"], v["ctx"]
    return t, steps


def _view(cell_name, t, steps):
    cell = spec.load_cell(cell_name)
    win = serving.Window(0.0, 1.0, [], steps)
    return cell, runner.RunView(cell, win, t, spec.peaks()["TPU v5 lite"],
                                cell.reference.dims(cell.config))


PREFILL_METRICS = {"prefill_ms_per_ktok", "prefill_mfu",
                   "flash_attention_roofline", "ssd_scan_roofline"}
CELLS = {"qwen2.5-3b.x2.chat": ("decode_attention", "flash_attention")}


@pytest.mark.parametrize("cell_name", list(CELLS))
def test_recorded_trace(cell_name):
    t, steps = _recorded(cell_name)
    runner.attribute(t, steps)
    decode = [m for m in t.step_modules("decode") if m.step >= 0]
    prefill = [m for m in t.step_modules("prefill") if m.step >= 0]
    assert decode
    assert all(m.prompt for m in prefill)
    dec_kernel, pre_kernel = CELLS[cell_name]
    if prefill:
        assert pre_kernel in {kernels.kernel_of(o.name)
                              for m in prefill for o in m.ops}
    if dec_kernel:
        assert dec_kernel in {kernels.kernel_of(o.name)
                              for m in decode for o in m.ops}
    assert 0 < t.busy_ns() < t.window_ns
    cell, view = _view(cell_name, t, steps)
    for m in cell.per_layer:
        if m["source"] != "device_trace":
            continue
        got = cell.reader(m["name"]).read(view)
        if not prefill and m["name"] in PREFILL_METRICS:
            assert got is None, m["name"]     # nothing to read
            continue
        value = got["value"] if isinstance(got, dict) else got
        assert value is not None and value > 0, m["name"]
        if m["unit"] == "%":
            assert value <= 100.0, (m["name"], value)
    gaps = runner.breakdown(t)
    assert len(gaps["device_ops"]) == 10
    assert all(name.startswith("chipbench.") or name == "no harness span"
               for name, _ in gaps["idle_gaps"])
