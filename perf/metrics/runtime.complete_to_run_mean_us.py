"""Mean time from an operation's completion to its continuation running,
over every continuation of the window (``obs`` histogram
``complete_to_run``, exact total over count; us)."""
EDGE = "complete_to_run"


def read(run):
    if run.obs is None:
        return None
    total = count = 0
    for (edge, _policy), h in run.obs.histograms().items():
        if edge == EDGE:
            total += h.total
            count += h.count
    return total / count if count else None
