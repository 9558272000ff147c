"""Device: share of the traced window in which no operation ran on the
chip (1 minus the union of the op intervals over the window), percent."""


def read(run):
    w = run.trace.window_ns
    if w <= 0 or run.trace.devices == 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_ns() / w)
