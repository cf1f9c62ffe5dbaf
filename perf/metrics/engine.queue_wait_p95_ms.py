"""95th percentile of the wait from arrival to placement (``req.admit``
span) over the requests due in the window that were placed (ms). None
where the tracer dropped events."""
from perf.drive import percentile
from perf.loop_trace import queue_waits


def read(run):
    waits = queue_waits(run)
    return 1e3 * percentile(waits, 0.95) if waits else None
