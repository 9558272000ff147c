"""Model step: device time of the prefill calls in the traced window per
thousand prompt tokens they processed."""


def read(run):
    mods = [m for m in run.step_modules("prefill") if m.prompt]
    if not mods:
        return None
    return (sum(m.dur for m in mods) * 1e-6
            / (sum(m.prompt for m in mods) / 1e3))
