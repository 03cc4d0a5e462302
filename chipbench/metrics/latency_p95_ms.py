"""latency_p95_ms (ms, host clock): the 95th percentile, over every
frame due in the window, of result back with the client minus the
frame's due time; a frame that failed or never came counts as slower
than every other (and then fails the run's check)."""
from harness.stats import percentile


def read(run):
    lat = [(f.done - f.due) if f.done is not None and f.error is None
           else float("inf") for f in run.window.frames]
    p = percentile(lat, 95)
    return None if p is None or p == float("inf") else p * 1e3
