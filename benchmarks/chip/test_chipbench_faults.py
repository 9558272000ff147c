"""The harness on the CPU, the device check skipped, with the timed path
broken underneath: a sound run is correct, and each fault a serving cell
can have makes ``correct`` false. Faults are planted in the decode step
the runtime compiles: a token altered where it is produced, a step that
returns its state unchanged, tokens handed to the wrong slots."""
import time

import pytest

from chipbench import runner
from chipbench.faults import FAULTS, planted
from chipbench_testkit import make_root

SEED = 2 ** 31 + 77


def run_with(monkeypatch, tmp, model: str, fault: str) -> dict:
    from repro.core import runtime
    if FAULTS[fault] is not None:
        monkeypatch.setattr(runtime, "lm_decode_sample", planted(fault))
    root, name = make_root(tmp, model)
    return runner.run(name, SEED, 2.0, False, time.perf_counter(), root=root,
                      need_chip=False)


@pytest.mark.parametrize("fault", list(FAULTS))
def test_attention_cell(monkeypatch, tmp_path, fault):
    out = run_with(monkeypatch, tmp_path, "qwen2", fault)
    assert out["correct"] is (fault == "sound"), out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] == 16 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p90_ms", "itl_p95_ms",
                                   "tokens_per_s", "setup_s"}
