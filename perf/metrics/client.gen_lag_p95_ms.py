"""How late the load thread sent open-loop requests: 95th percentile of
send minus schedule over the requests due in the window (ms). A starved
generator reads here, not as a slow server."""
from perf.drive import percentile


def read(run):
    if run.traffic["loop"] != "open":
        return None
    lags = [r.sent - r.due for r in run.records if run.ws <= r.due < run.we]
    return 1e3 * percentile(lags, 0.95) if lags else None
