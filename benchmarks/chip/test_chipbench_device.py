"""The benchmark refuses a CPU, a device missing from its peaks table, too
few chips, and a checkout that holds only the benchmark."""
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import device, spec

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
ARGS = ["--workload", "qwen2.5-3b.x2.chat", "--seed", "2147483999",
        "--seconds", "1", "--trace", "0"]


def _run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "benchmarks/chip/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=120)


def test_cpu_is_refused():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr and "'cpu'" in p.stderr


def test_benchmark_alone_is_refused(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def _fake(monkeypatch, kind, n):
    import jax
    devs = [SimpleNamespace(platform="tpu", device_kind=kind)] * n
    monkeypatch.setattr(jax, "devices", lambda *a: devs)


def test_unknown_tpu_is_refused(monkeypatch):
    _fake(monkeypatch, "TPU v9 imaginary", 1)
    with pytest.raises(device.NoChip, match="peaks table"):
        device.require(1, spec.peaks())


def test_too_few_chips_are_refused(monkeypatch):
    _fake(monkeypatch, "TPU v5 lite", 1)
    with pytest.raises(device.NoChip, match="needs 4"):
        device.require(4, spec.peaks())
    _fake(monkeypatch, "TPU v5 lite", 4)
    assert device.require(4, spec.peaks()) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}
