"""Wall time of a step dispatch on the loop thread: sweep, page-table
upload, launch and per-slot token pushes, mean over the window's
dispatches that launched a step (``obs`` histogram ``("loop",
"dispatch")``, total over count; ms). While a push waits for the device
to take the next launch, this includes that wait: a faster step moves it
as a cheaper dispatch does."""
KEY = ("loop", "dispatch")


def read(run):
    if run.obs is None:
        return None
    h = run.obs.histograms().get(KEY)
    return 1e-3 * h.total / h.count if h is not None and h.count else None
