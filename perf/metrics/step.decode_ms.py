"""Device time per launch of the fused decode program in the traced
window (ms). The suffix program is also a jitted ``step``, so the number
is read only where the window ran no suffix step."""
PROGRAM = "jit_step"


def read(run):
    if run.trace is None or \
            run.stats1["suffix_steps"] != run.stats0["suffix_steps"]:
        return None
    p = run.trace["programs"].get(PROGRAM)
    if not p or not p["launches"]:
        return None
    return 1e3 * p["seconds"] / p["launches"]
