"""Load generator: 99th percentile of how late each request was submitted
(submit time minus due time), host clock. A starved generator shows here
and not as a fast server. Only requests submitted before the profiler
started count: starting it stalls the process."""
from chipbench.stats import percentile


def read(run):
    start = run.window.trace_start
    lags = [(r.submitted - r.due) * 1e3 for r in run.window.records
            if r.submitted < start]
    return percentile(lags, 99) if lags else None
