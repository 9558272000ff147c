"""As ``test_chipbench_faults.py``, for the state-space configuration: a
sound run of a tiny mamba2 cell is correct, and each planted fault in its
decode step makes ``correct`` false. A file of its own, so that the two
configurations' runs go to different test workers."""
import pytest

from test_chipbench_faults import FAULTS, run_with


@pytest.mark.parametrize("fault", list(FAULTS))
def test_state_space_cell(monkeypatch, tmp_path, fault):
    out = run_with(monkeypatch, tmp_path, "mamba2", fault)
    assert out["correct"] is (fault == "sound"), out["checks"]
    assert list(out)[-1] == "checks"
    assert out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p90_ms", "itl_p95_ms",
                                   "tokens_per_s", "setup_s"}
