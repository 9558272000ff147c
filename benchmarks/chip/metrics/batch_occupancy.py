"""Scheduler: mean number of active slots per decode call in the window,
read from the batchers' active slots after each step."""


def read(run):
    w = run.window
    close = w.t0 + w.seconds
    sizes = [len(s.ctx) for s in w.steps if s.ctx and s.start < close]
    return sum(sizes) / len(sizes) if sizes else None
