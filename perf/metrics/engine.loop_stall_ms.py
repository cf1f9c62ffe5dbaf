"""The longest single phase of the serve loop in the window: the largest
``max`` over the ``obs`` histograms ``("loop", <phase>)`` of admission,
dispatch, progress and commit (ms)."""
EDGE = "loop"


def read(run):
    if run.obs is None:
        return None
    longest = [h.max for (edge, _phase), h in run.obs.histograms().items()
               if edge == EDGE and h.count]
    return 1e-3 * max(longest) if longest else None
