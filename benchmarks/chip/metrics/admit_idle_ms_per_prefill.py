"""Scheduler: device idle time charged to the batcher's admissions
(``hydra.lm.admit``: prompt upload, prefill dispatch, first-token
readback; see ``chipbench/phases.py``) per admission begun in the traced
window, ms. Reads nothing where the program opens no such spans."""
from chipbench import phases


def read(run):
    got = phases.charged(run)
    admits = phases.started_in_window(run, "lm.admit")
    if got is None or not admits:
        return None
    return got.get("lm.admit", 0.0) / len(admits) * 1e-6
