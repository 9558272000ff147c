"""Model step: device time per decode call (one execution of the decode
program on the chip), mean over the traced window."""


def read(run):
    mods = run.step_modules("decode")
    if not mods:
        return None
    return sum(m.dur for m in mods) / len(mods) * 1e-6
