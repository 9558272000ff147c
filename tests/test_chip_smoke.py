"""chip_smoke.py: it refuses to run without a TPU, and its LM and fleet
phases pass at the reduced width on CPU with interpret-mode kernels (the
rehearsal of the chip run)."""
import os
import subprocess
import sys

import pytest

import chip_smoke
from repro.kernels.ops import set_kernel_mode

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_refuses_cpu_before_any_work(tmp_path, where):
    script = os.path.join(REPO, "chip_smoke.py")
    if where == "alone":
        alone = tmp_path / "chip_smoke.py"
        alone.write_text(open(script).read())
        script = str(alone)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, script], capture_output=True,
                          text=True, timeout=120, env=env,
                          cwd=os.path.dirname(script))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "runs only on a TPU" in proc.stderr


def test_lm_phase_reduced_on_cpu():
    set_kernel_mode("interpret")
    try:
        out = chip_smoke.lm_phase(
            ["--archs", "qwen2.5-3b", "--reduced", "--tenants", "2",
             "--pool", "2", "--requests", "4", "--prompt-len", "16",
             "--max-new", "5", "--slots", "4", "--max-seq", "64"])
    finally:
        set_kernel_mode("auto")
    assert out["tokens"] == 4 * 5
    assert out["loop_compiles"] == 0
    assert out["exe_cache"]["compiles"] == 3  # decode, prefill, zeroer
    assert set(out["hlo"]) == {"prefill", "decode"}
    assert out["logit_err"] <= chip_smoke.LOGIT_TOL * out["logit_scale"]


def test_fleet_phase_on_cpu():
    argv = list(chip_smoke.FLEET_ARGV)
    argv[argv.index("--max-minutes") + 1] = "2"
    summary = chip_smoke.fleet_phase(argv)
    assert summary["requests"] == summary["submitted"] > 0
