"""Share of decode slot-steps in the window that carried a request:
engine ``slot_steps / (slot_steps + padded_steps)`` (%)."""


def read(run):
    used = run.stats1["slot_steps"] - run.stats0["slot_steps"]
    pad = run.stats1["padded_steps"] - run.stats0["padded_steps"]
    return 100.0 * used / (used + pad) if used + pad else None
