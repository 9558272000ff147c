"""Scheduler: device idle time charged to the batcher's token readback
(``hydra.lm.sync``, see ``chipbench/phases.py``) per decode call in the
traced window, ms. Reads nothing where the program opens no such spans."""
from chipbench import phases


def read(run):
    got = phases.charged(run)
    decodes = run.step_modules("decode")
    if got is None or not decodes:
        return None
    return got.get("lm.sync", 0.0) / len(decodes) * 1e-6
