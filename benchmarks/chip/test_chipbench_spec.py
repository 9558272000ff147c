"""BENCHMARK.json keeps to the benchmark's contract, and a cell's
configuration, traffic mix and metric readers are found by name: a new
cell needs new files and entries only."""
import json
import re
from pathlib import Path

import pytest

from chipbench import spec
from chipbench_testkit import make_root

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks/chip"]
    assert 1 <= bench["run_seconds"] <= 51
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 \
        + 1200 <= 43200


def test_names_units_and_entries(bench):
    names = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
            assert e["name"] not in names
            names.add(e["name"])
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in (
                    "lower", "higher")
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        assert "\n" not in m["layer"]


def test_every_cell_finds_its_files(bench):
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], ROOT)
        assert cell.chips == 1
        assert cell.reference.layout(cell.config)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]).read)


def test_config_files_hold_what_is_run(bench):
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        for key in c["reduced"]:
            assert key in cfg["config"] and key in cfg["assumed"]


def test_new_cell_is_files_only(tmp_path):
    root, _ = make_root(tmp_path, "qwen2")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    (root / "bench" / "traffic" / "other.json").write_text(
        (root / "bench" / "traffic" / "mix.json").read_text())
    bench["workloads"].append({"name": "tiny-qwen2.other",
                               "config": "tiny-qwen2", "traffic": "other",
                               "chips": 1, "why": "test"})
    extra = tmp_path / "extra_metric.py"
    extra.write_text("def read(run):\n    return 42.0\n")
    bench["per_layer"].append({"name": "extra_metric", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "test", "moves": "setup_s",
                               "workloads": ["tiny-qwen2.other"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("tiny-qwen2.other", root)
    assert cell.traffic["arrivals"]["mean_rate_per_s"] == 8.0
    assert [m["name"] for m in cell.per_layer][-1] == "extra_metric"
    assert spec.load_module(extra).read(None) == 42.0
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell", root)


def test_config_without_limit_is_refused(tmp_path):
    """A configuration whose gap limit has not been read is refused
    before any set-up, and every offered configuration has one."""
    import time

    from chipbench import check, runner
    root, name = make_root(tmp_path, "qwen2")
    path = root / "bench" / "configs" / "tiny-qwen2.json"
    cfg = json.loads(path.read_text())
    cfg["check"]["gap_limit"] = None
    path.write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match="no gap_limit"):
        runner.run(name, 1, 1.0, False, time.perf_counter(), root=root,
                   need_chip=False)
    for c in json.loads((ROOT / "BENCHMARK.json").read_text())["configs"]:
        assert check.gap_limit(json.loads((ROOT / c["file"]).read_text()))
