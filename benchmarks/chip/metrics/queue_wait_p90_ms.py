"""Scheduler: 90th percentile of the wait from a request's due time to the
start of the batcher step that admitted it (prefilled it into a slot),
host clock. Only requests admitted before the profiler started count:
starting and stopping it stall the loop."""
from chipbench.stats import percentile


def read(run):
    start = run.window.trace_start
    waits = [(r.admitted - r.due) * 1e3 for r in run.window.records
             if r.admitted < start]
    return percentile(waits, 90) if waits else None
